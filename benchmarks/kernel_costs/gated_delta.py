"""Operations and bytes the gated delta rule's sequential pass needs
(`gdn_state_fwd`, `gdn_state_bwd`: kernels/pallas/gated_delta.py), for
one call on `heads` (batch x value heads) sequences of `chunks` chunks of
C tokens, key size dk, value size dv, float32 (`ops.gated_delta_rule` is
on amp's black list).

With S [dk, dv] the state entering a chunk: V' = U - W S, O = Qg S +
P V', S <- a S + Kd^T V'. forward, a chunk and head: the three products
with S (2 C dk dv each) and the masked one (2 C C dv); it reads U, W,
Qg, Kd, P and a once, writes O and the state entering the chunk (dk dv
numbers, the backward's residual: 64 KB at 128 x 128, more than a
chunk's O). backward: dV' = P^T dO + Kd dS', dP = dO V'^T, dQg = dO S^T,
dKd = V' dS'^T, dW = -dV' S^T, dS = Qg^T dO + a dS' - W^T dV': six
products with a state-sized side and two masked-sized ones (V', which it
makes again, is recomputation and not counted); it reads the forward's
operands, the saved state and dO, writes the six gradients. The bytes
bind: the state a chunk is read or written once whatever the products
cost.

`chunk_operations` is the whole chunked algorithm's count forward, a
chunk and head, for `harness/qwen3next_flops.py`: the pass's four
products, K K^T and Q K^T, U and W (a [C, C] matrix on [C, d]) and the
six [C, C] products of the triangular inverse's doubling."""

KERNELS = {"gdn_state_fwd": "fwd", "gdn_state_bwd": "bwd"}


def classify(component: str):
    """Which kernel an operation is, by the scope path the program gave
    it (`…/layers/1/gdn/delta_rule/gdn_state_fwd`: a `pallas_call`'s
    name is its innermost `jax.named_scope`), or None."""
    return KERNELS.get(component.rsplit("/", 1)[-1])


def cost(kind: str, heads: int, chunks: int, C: int, dk: int, dv: int,
         item: int = 4):
    """(operations, bytes) of one call."""
    n = heads * chunks
    state, masked = 2.0 * C * dk * dv, 2.0 * C * C * dv
    operands = (C * dv + 3 * C * dk + C * C + 1) * item     # U W Qg Kd P a
    if kind == "fwd":
        return (n * (3 * state + masked),
                n * (operands + (C * dv + dk * dv) * item))
    if kind == "bwd":
        return (n * (6 * state + 2 * masked),
                n * (2 * operands + (C * dv + dk * dv) * item))
    raise KeyError(kind)


def chunk_operations(C: int, dk: int, dv: int) -> float:
    """Forward operations of the chunked algorithm, a chunk and head."""
    state, masked = 2.0 * C * dk * dv, 2.0 * C * C * dv
    scores = 2 * 2.0 * C * C * dk               # K K^T, Q K^T
    solve = 6 * 2.0 * C * C * C                 # the inverse's products
    applied = 2.0 * C * C * dv + 2.0 * C * C * dk           # U, W
    return 3 * state + masked + scores + solve + applied
