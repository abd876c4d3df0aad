"""Operations and bytes the selective-scan kernels' algorithm needs, for
one call on x, delta [rows, seq, channels], A [channels, state], B, C
[rows, seq, state], D [channels] (`ops.selective_scan`: on amp's black
list, so every operand is float32 in a training step; `x_bytes` says
otherwise).

forward, per element of the state and time step: delta*A, its
exponential, a*h, B*(delta x), their sum, h*C and its sum over the state:
7; per channel and step: delta*x, D*x and its sum into y: 3. It reads x,
delta, A, B, C and D and writes y.
backward, per element of the state and step: C*dy and its sum into the
state's adjoint g, h*dy and g*(delta x) and their sums over the channels
(dC, dB), g*B and its sum (the adjoint of delta*x), delta*A and its
exponential, g*h, *a, *A and its sum (d delta), *delta and its sum into
dA, a*g: 17; per channel and step: d delta's and dx's two products and
sums, dy*x and its sum into dD: 8. The states it makes again from the
saved chunk starts are recomputation and not counted, nor are the chunk
starts' bytes: an implementation's choice, not the algorithm's need. It
reads x, delta, dy, A, B, C and D and writes dx, d delta, dA, dB, dC, dD.

The recurrence runs on the vector unit, for which `harness/peaks.json`
has no rate: `peaks.least_seconds` compares the operations with the
matrix unit's rate, which they never bind, so the least time is the
bytes'."""

KERNELS = {"ssm_scan_fwd": "fwd", "ssm_scan_bwd": "bwd"}


def classify(component: str):
    """Which scan kernel an operation is, by the scope path the program
    gave it (`…/layers/3/mamba/ssm_scan_fwd`: a `pallas_call`'s name is
    its innermost `jax.named_scope`), or None. Forward kernels run
    again by `jax.checkpoint` keep the path."""
    return KERNELS.get(component.rsplit("/", 1)[-1])


def cost(kind: str, rows: int, seq: int, channels: int, state: int,
         x_bytes: int = 4):
    """(operations, bytes) of one call."""
    steps = rows * seq
    tall, wide = steps * channels, steps * state     # x-like, B-like
    table = channels * state
    if kind == "fwd":
        return (7.0 * tall * state + 3.0 * tall,
                tall * (2 * x_bytes + 4) + 2 * wide * 4
                + (table + channels) * 4)
    if kind == "bwd":
        return (17.0 * tall * state + 8.0 * tall,
                tall * (3 * x_bytes + 8) + 4 * wide * 4
                + 2 * (table + channels) * 4)
    raise KeyError(kind)
