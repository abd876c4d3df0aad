"""The gated delta rule in chunks: the chunk preparation and the
sequential pass as Pallas TPU kernels, forward and backward.

A head's recurrence over tokens (Yang, Kautz & Hatamizadeh 2024, "Gated
Delta Networks"), S in R^{dk x dv} float32 from S_0 = 0:

    S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

In a chunk of C tokens, with gam_i = sum_{j<=i} g_j:

    A = -strict_tril(beta_i (k_i . k_j) e^{gam_i - gam_j})
    T = (I - A)^{-1};  U = T (beta * V);  W = T (beta * K * e^{gam})
    P = tril(Q K^T * e^{gam_i - gam_j});  Qg = Q * e^{gam}
    Kd = K * e^{gam_C - gam};  a = e^{gam_C}

and, with S the state entering the chunk:

    V' = U - W S;   O = Qg S + P V';   S <- a S + Kd^T V'

`prepare` states the first block for every chunk of `CHUNK` tokens at
once (float32, the products at `highest` precision); it runs scoped
`prepare`. Only the three lines with S are sequential: the state pass
(`_state_vjp_fwd`, `_state_vjp_bwd`). `gated_delta_rule` is one
`jax.custom_vjp` over both.

* the preparation (`gdn_prepare_fwd`, `gdn_prepare_bwd`): no state is
  carried, so a grid step takes any `PAIRS` pairs of chunks and every
  step is free. A chunk's masks, K K^T, Q K^T, A, the inverse's powers
  and partial products stay in VMEM; out go U, W, Qg, Kd, P, a and T,
  which the backward kernel reads with the state pass's six gradients
  (`_pair_bwd` has its formulas). Off a TPU `prepare` runs in XLA under
  autodiff (`prepare_path`).

* forward (`gdn_state_fwd`): grid (batch x head in blocks of `HEADS`,
  chunk), the chunk axis sequential, S a [dk, dv] float32 scratch a
  head; writes O and the state entering each chunk (the backward's
  residual: dk x dv x 4 bytes a chunk and head).
* backward (`gdn_state_bwd`): the chunks in reverse, carrying dS. With
  dS' the gradient of the state leaving the chunk:
    dV' = P^T dO + Kd dS';  dP = dO V'^T;  dQg = dO S^T;  dKd = V' dS'^T
    da = <dS', S>;  dU = dV';  dW = -dV' S^T
    dS = Qg^T dO + a dS' - W^T dV'
  (dP is the gradient to every entry of P; `prepare`'s mask zeroes the
  upper ones under autodiff.)

q and k may come at Hk key heads where v, g and beta have H value heads
(Hk dividing H: a Gated DeltaNet's 16 on 32). The preparation's grid
then runs a key head's steps by its H / Hk value heads: the value
head's step reads its key head's block of q and k where it lies, and
the backward adds the value heads' dq and dk in the key head's output
block. Nothing is copied a value head, forward or back. The operands
reach `_rule` heads first and in chunks ([B, nc, C, d]): from tokens
first through `gated_delta_rule`, or as `gdn_operands.py` wrote them
through `gated_delta_rule_heads_first`.

A block holds `HEADS` heads whose chains are independent: the scheduler
overlaps one head's products with another's. On a TPU backend the
kernels are the only path (`state_path`, `prepare_path`). Elsewhere (the
CPU tests) the same three lines and the same backward formulas run as a
`lax.scan` over chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability import perf as _pf
from .flash_attention import _pallas_available

CHUNK = 64          # tokens a chunk: the one value any caller runs
HEADS = 4           # heads a kernel instance holds
_VMEM_LIMIT = 64 * 1024 * 1024
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

# dot_general dimension numbers of 2-D operands
_NN = (((1,), (0,)), ((), ()))      # x @ y
_NT = (((1,), (1,)), ((), ()))      # x @ y^T
_TN = (((0,), (0,)), ((), ()))      # x^T @ y


def _dot(x, y, dims):
    return jax.lax.dot_general(x, y, dims, precision=_HI,
                               preferred_element_type=F32)


# ======================= one chunk, one head =======================

def _chunk_fwd(S, u, w, qg, kd, p, a):
    """-> (O [C, dv], the state leaving the chunk). a: a scalar or a
    [1, dv] row of one value."""
    vp = u - _dot(w, S, _NN)
    o = _dot(qg, S, _NN) + _dot(p, vp, _NN)
    return o, a * S + _dot(kd, vp, _TN)


def _chunk_bwd(S, dS2, u, w, qg, kd, p, a, do):
    """-> (dU, dW, dQg, dKd, dP, <dS', S> summed over rows only [1, dv],
    dS)."""
    vp = u - _dot(w, S, _NN)
    dvp = _dot(p, do, _TN) + _dot(kd, dS2, _NN)
    dS = _dot(qg, do, _TN) + a * dS2 - _dot(w, dvp, _TN)
    return (dvp, -_dot(dvp, S, _NT), _dot(do, S, _NT), _dot(vp, dS2, _NT),
            _dot(do, vp, _NT), jnp.sum(dS2 * S, axis=0, keepdims=True), dS)


# ======================= the kernels =======================

def _fwd_kernel(u_ref, w_ref, qg_ref, kd_ref, p_ref, a_ref, o_ref, hs_ref,
                s_sc, *, heads):
    @pl.when(pl.program_id(1) == 0)
    def _start():
        s_sc[...] = jnp.zeros(s_sc.shape, F32)

    for h in range(heads):
        S = s_sc[h]
        hs_ref[h, 0] = S
        o, s_sc[h] = _chunk_fwd(S, u_ref[h, 0], w_ref[h, 0], qg_ref[h, 0],
                                kd_ref[h, 0], p_ref[h, 0], a_ref[h, 0])
        o_ref[h, 0] = o.astype(o_ref.dtype)


def _bwd_kernel(u_ref, w_ref, qg_ref, kd_ref, p_ref, a_ref, hs_ref, do_ref,
                du_ref, dw_ref, dqg_ref, dkd_ref, dp_ref, da_ref, ds_sc, *,
                heads):
    @pl.when(pl.program_id(1) == 0)     # the last chunk: nothing follows
    def _start():
        ds_sc[...] = jnp.zeros(ds_sc.shape, F32)

    for h in range(heads):
        (du_ref[h, 0], dw_ref[h, 0], dqg_ref[h, 0], dkd_ref[h, 0],
         dp_ref[h, 0], da_ref[h, 0], ds_sc[h]) = _chunk_bwd(
            hs_ref[h, 0], ds_sc[h], u_ref[h, 0], w_ref[h, 0], qg_ref[h, 0],
            kd_ref[h, 0], p_ref[h, 0], a_ref[h, 0],
            do_ref[h, 0].astype(F32))


def _specs(heads, C, dk, dv, nc, reverse):
    def at(rows, cols):
        return pl.BlockSpec(
            (heads, 1, rows, cols),
            (lambda i, j: (i, nc - 1 - j, 0, 0)) if reverse
            else (lambda i, j: (i, j, 0, 0)))
    return dict(k=at(C, dk), v=at(C, dv), p=at(C, C), a=at(1, dv),
                state=at(dk, dv))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _heads(B):
    return next(h for h in (HEADS, 2, 1) if B % h == 0)


@_pf.trace_timed_call("gdn_state_fwd")
def _state_fwd_pallas(U, W, Qg, Kd, P, a, interpret=False):
    """U [B, nc, C, dv]; W, Qg, Kd [B, nc, C, dk]; P [B, nc, C, C];
    a [B, nc, 1, dv] (a chunk's value on every lane), all float32 ->
    (O like U, the state entering every chunk [B, nc, dk, dv])."""
    B, nc, C, dv = U.shape
    dk = W.shape[-1]
    heads = _heads(B)
    s = _specs(heads, C, dk, dv, nc, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads),
        grid=(B // heads, nc),
        in_specs=[s["v"], s["k"], s["k"], s["k"], s["p"], s["a"]],
        out_specs=[s["v"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct(U.shape, U.dtype),
                   jax.ShapeDtypeStruct((B, nc, dk, dv), F32)],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="gdn_state_fwd",   # also the innermost jax.named_scope
    )(U, W, Qg, Kd, P, a)


@_pf.trace_timed_call("gdn_state_bwd")
def _state_bwd_pallas(U, W, Qg, Kd, P, a, states, dO, interpret=False):
    """-> dU, dW, dQg, dKd, dP like their operands, da as partials over
    the lanes [B, nc, 1, dv]."""
    B, nc, C, dv = U.shape
    dk = W.shape[-1]
    heads = _heads(B)
    s = _specs(heads, C, dk, dv, nc, True)
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads),
        grid=(B // heads, nc),
        in_specs=[s["v"], s["k"], s["k"], s["k"], s["p"], s["a"],
                  s["state"], s["v"]],
        out_specs=[s["v"], s["k"], s["k"], s["k"], s["p"], s["a"]],
        out_shape=[like(U.shape, F32), like(W.shape, F32),
                   like(Qg.shape, F32), like(Kd.shape, F32),
                   like(P.shape, F32), like(a.shape, F32)],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="gdn_state_bwd",
    )(U, W, Qg, Kd, P, a, states, dO)


# ======================= the same, without a kernel =======================

def _chunks_first(xs):
    return tuple(jnp.moveaxis(x, 1, 0) for x in xs)


def _state_fwd_xla(U, W, Qg, Kd, P, a):
    def chunk(S, xs):
        o, S2 = jax.vmap(_chunk_fwd)(S, *xs)
        return S2, (o, S)

    B, _nc, _C, dv = U.shape
    _S, (O, states) = jax.lax.scan(
        chunk, jnp.zeros((B, W.shape[-1], dv), F32),
        _chunks_first((U, W, Qg, Kd, P, a)))
    return jnp.moveaxis(O, 0, 1), jnp.moveaxis(states, 0, 1)


def _state_bwd_xla(U, W, Qg, Kd, P, a, states, dO):
    def chunk(dS, xs):
        S, *rest = xs
        *grads, dS = jax.vmap(_chunk_bwd)(S, dS, *rest)
        return dS, tuple(grads)

    _dS, grads = jax.lax.scan(
        chunk, jnp.zeros_like(states[:, 0]),
        _chunks_first((states, U, W, Qg, Kd, P, a, dO)), reverse=True)
    return tuple(jnp.moveaxis(g, 0, 1) for g in grads)


# ======================= the state pass =======================

def _spread(a, dv):
    """[B, nc] -> [B, nc, 1, dv]: a chunk's value on every lane."""
    return jnp.broadcast_to(a[..., None, None], a.shape + (1, dv))


def _state_vjp_fwd(U, W, Qg, Kd, P, a, mode):
    """The three sequential lines over the chunks of every head. U
    [B, nc, C, dv]; W, Qg, Kd [B, nc, C, dk]; P [B, nc, C, C]; a
    [B, nc], float32 -> (O [B, nc, C, dv], what the backward needs: the
    operands and the state entering each chunk). `mode`: `state_path()`'s
    "pallas" or "lax.scan", or "interpret" (the kernels in Pallas's
    interpreter: the CPU tests)."""
    wide = _spread(a, U.shape[-1])
    if mode == "lax.scan":
        O, states = _state_fwd_xla(U, W, Qg, Kd, P, wide)
    else:
        O, states = _state_fwd_pallas(U, W, Qg, Kd, P, wide,
                                      interpret=mode == "interpret")
    return O, (U, W, Qg, Kd, P, a, states)


def _state_vjp_bwd(mode, res, dO):
    """-> the gradients to U, W, Qg, Kd, P and a."""
    U, W, Qg, Kd, P, a, states = res
    wide = _spread(a, U.shape[-1])
    run = _state_bwd_xla if mode == "lax.scan" else functools.partial(
        _state_bwd_pallas, interpret=mode == "interpret")
    *grads, da = run(U, W, Qg, Kd, P, wide, states, dO.astype(F32))
    return (*grads, jnp.sum(da, axis=(-2, -1)))


# ======================= the chunk preparation =======================

@jax.custom_vjp
def _inverse(A):
    """(I - A)^{-1} of strictly lower triangular A [..., C, C]: A is
    nilpotent, so the inverse is (I + A)(I + A^2)(I + A^4) ... up to
    A^(C/2): log2 C products and as many squarings."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=A.dtype)
    T, power, n = eye + A, A, 1
    while 2 * n < C:
        power = jnp.matmul(power, power, precision=_HI)
        T = jnp.matmul(T, eye + power, precision=_HI)
        n *= 2
    return T


def _inverse_fwd(A):
    T = _inverse(A)
    return T, T


def _inverse_bwd(T, dT):
    # d(I - A)^{-1} = T dA T
    Tt = jnp.swapaxes(T, -1, -2)
    return (jnp.matmul(jnp.matmul(Tt, dT, precision=_HI), Tt,
                       precision=_HI),)


_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _heads_first(x):
    """[b, s, H, ...] -> [b * H, nc, CHUNK, ...]."""
    x = jnp.moveaxis(x, 2, 1)
    return x.reshape((-1, x.shape[2] // CHUNK, CHUNK) + x.shape[3:])


def _tokens_first(O, b):
    """[b * H, nc, C, dv] -> [b, s, H, dv]."""
    return jnp.moveaxis(O.reshape((b, O.shape[0] // b, -1, O.shape[-1])),
                        1, 2)


def _at_value_heads(q, k, B):
    """q and k at their key heads [Bk, ...] -> a copy a value head
    [B, ...] (a key head serves B / Bk value heads that follow each
    other)."""
    rep = B // q.shape[0]
    if rep == 1:
        return q, k
    return jnp.repeat(q, rep, axis=0), jnp.repeat(k, rep, axis=0)


def _prepare(q, k, v, g, beta):
    """`prepare` and the chunks' T [B, nc, C, C] behind it."""
    chunk = CHUNK
    q, k = _at_value_heads(q, k, v.shape[0])
    gam = jnp.cumsum(g, axis=-1)                        # [B, nc, C]
    rows = jnp.arange(chunk)
    seen = rows[:, None] >= rows[None, :]
    # e^{gam_i - gam_j} where i >= j (at most 1), 0 above the diagonal
    decay = jnp.exp(jnp.where(seen, gam[..., :, None] - gam[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("bnid,bnjd->bnij", k, k, precision=_HI)
    A = -jnp.where(rows[:, None] > rows[None, :],
                   beta[..., None] * kk * decay, 0.0)
    T = _inverse(A)
    e_gam = jnp.exp(gam)[..., None]
    U = jnp.matmul(T, beta[..., None] * v, precision=_HI)
    W = jnp.matmul(T, beta[..., None] * k * e_gam, precision=_HI)
    P = jnp.einsum("bnid,bnjd->bnij", q, k, precision=_HI) * decay
    last = gam[..., -1]
    Kd = k * jnp.exp(last[..., None] - gam)[..., None]
    return U, W, q * e_gam, Kd, P, jnp.exp(last), T


def prepare(q, k, v, g, beta):
    """The operands heads first and in chunks (`_heads_first`): q, k
    [Bk, nc, C, dk], v [B, nc, C, dv], g, beta [B, nc, C], float32, B = b
    * H value heads and Bk = b * Hk key heads, Hk dividing H -> (U, W,
    Qg, Kd, P, a) as the state pass takes them. The plain statement of
    the chunk preparation: the path off a TPU, under autodiff, and what
    the kernels are held to."""
    return _prepare(q, k, v, g, beta)[:6]


# ======================= the preparation as kernels =======================
#
# A grid step holds PAIRS pairs of chunks; no state is carried, so any two
# chunks make a pair and every step is free. A pair's tokens stand stacked
# ([2C, d]: q, k, v, U, W, Qg, Kd and their gradients), its [C, C] matrices
# side by side ([C, 2C]: the decay mask, A, P, T and the inverse's powers),
# so that they fill the 128 lanes and one product against a block-diagonal
# [2C, 2C] right operand squares or multiplies both at once. g and beta
# come as rows ([8, 2C] a step, the first PAIRS used); what scales a
# token's row is their transpose's column.

PAIRS = 4           # pairs of chunks a grid step holds (at most _ROWS)
_ROWS = 8           # rows of a step's block of g, beta and their gradients


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _same_half(C):
    """[2C, 2C]: row and column in the same chunk of the pair."""
    r, c = _iota((2 * C, 2 * C), 0), _iota((2 * C, 2 * C), 1)
    return (r < C) == (c < C), r, c


def _pair_masks(C):
    """Of a [C, 2C] pair of matrices: the left one's lanes, i >= j, i > j,
    i == j."""
    rows, lanes = _iota((C, 2 * C), 0), _iota((C, 2 * C), 1)
    left = lanes < C
    col = jnp.where(left, lanes, lanes - C)
    return left, rows >= col, rows > col, rows == col


def _bd(x, left):
    """[X1 | X2] -> [[X1, 0], [0, X2]]."""
    return jnp.concatenate([jnp.where(left, x, 0.0),
                            jnp.where(left, 0.0, x)], axis=0)


def _side(x, left):
    """The diagonal blocks of a [2C, 2C] matrix, side by side."""
    C = x.shape[0] // 2
    return jnp.where(left, x[:C], x[C:])


def _pair_rows(g, beta):
    """g, beta [_ROWS, 2C], a pair a row -> (gam, the running sum of g in
    each chunk; last, a chunk's whole sum on each of its lanes; cols
    [2C, 2C], whose column t * _ROWS + n is pair n's gam, beta, e^gam,
    e^{last - gam} for t = 0 .. 3, down the pair's stacked tokens)."""
    C = g.shape[1] // 2
    same, r, c = _same_half(C)
    gam = _dot(g, jnp.where(same & (r <= c), 1.0, 0.0), _NN)
    lanes = _iota(g.shape, 1)
    last = jnp.where(lanes < C, gam[:, C - 1:C], gam[:, 2 * C - 1:])
    rows = [gam, beta, jnp.exp(gam), jnp.exp(last - gam)]
    rows.append(jnp.zeros((2 * C - len(rows) * _ROWS, 2 * C), F32))
    return gam, last, jnp.concatenate(rows, axis=0).T


def _pair_cols(cols, n, left):
    """Pair n's columns of `_pair_rows`' cols: gam beside each matrix of
    the pair [C, 2C]; beta, e^gam, e^{last - gam} [2C, 1]."""
    C = cols.shape[0] // 2
    gam, beta, eg, ekd = (cols[:, t * _ROWS + n:t * _ROWS + n + 1]
                          for t in range(4))
    return jnp.where(left, gam[:C], gam[C:]), beta, eg, ekd


def _pair_scores(q, k, gam_row, gc, bc, masks):
    """-> decay, beta * K, A, P of a pair: the masks and the two score
    products (one product: beta K and Q stacked against K)."""
    left, seen, strict, _eye = masks
    # e^{gam_i - gam_j} where i >= j (at most 1), 0 above the diagonal
    decay = jnp.where(seen, jnp.exp(gc - gam_row), 0.0)
    kb = k * bc
    S = _dot(jnp.concatenate([kb, q], axis=0), k, _NT)
    n = k.shape[0]
    A = jnp.where(strict, -_side(S[:n], left) * decay, 0.0)
    return decay, kb, A, _side(S[n:], left) * decay


def _pair_inverse(A, left, eye):
    """`_inverse`'s doubling on both matrices of a pair at once. A power
    squares and multiplies T in one product (the two stacked against
    the power): T (I + A^n) as T + T A^n."""
    C = A.shape[0]
    T, power, n = jnp.where(eye, 1.0, 0.0) + A, _dot(A, _bd(A, left), _NN), 2
    while 2 * n < C:
        both = _dot(jnp.concatenate([power, T], axis=0), _bd(power, left),
                    _NN)
        T, power, n = T + both[C:], both[:C], 2 * n
    return T + _dot(T, _bd(power, left), _NN)


def _pair_fwd(q, k, v, gam_row, gc, bc, eg, ekd, masks):
    """`prepare` for one pair of chunks -> U, W, Qg, Kd [2C, d], P and T
    [C, 2C]."""
    left, _seen, _strict, eye = masks
    _decay, kb, A, P = _pair_scores(q, k, gam_row, gc, bc, masks)
    T = _pair_inverse(A, left, eye)
    both = _bd(T, left)
    return (_dot(both, v * bc, _NN), _dot(both, kb * eg, _NN), q * eg,
            k * ekd, P, T)


def _pair_bwd(q, k, v, gam_row, gc, bc, eg, ekd, T, dU, dW, dQg, dKd, dP,
              masks):
    """`prepare`'s transpose for one pair, T read and not remade. With
    bV = beta V, bKg = beta K e^gam, and M the strict lower mask:
        dT = dU bV^T + dW bKg^T;  dbV = T^T dU;  dbKg = T^T dW
        dA = M * (T^T dT T^T)            (`_inverse_bwd`)
        d(beta K K^T) = -dA * decay;  d(Q K^T) = dP * decay
        d decay * decay = E = dP * P + dA * A
        dgam_i = sum_j E_ij - sum_j E_ji + rows of (dbKg * bKg + dQg * Qg
                 - dKd * Kd);  dlast = sum(dKd * Kd) (+ da a, the caller's)
    -> dq, dk, dv [2C, d]; columns [2C, 1]: what reaches gam through a
    token's row, dbeta; rows [1, 2C]: what reaches gam through a token's
    column of E and through last."""
    left, _seen, strict, _eye = masks
    decay, kb, A, P = _pair_scores(q, k, gam_row, gc, bc, masks)
    C = T.shape[0]
    bkg = kb * eg
    bothT = _bd(T, left).T                      # [[T1^T, 0], [0, T2^T]]
    dT = _side(_dot(dU, v * bc, _NT) + _dot(dW, bkg, _NT), left)
    dbv, dbkg = _dot(bothT, dU, _NN), _dot(bothT, dW, _NN)
    dA = _dot(_dot(bothT[:C] + bothT[C:], _bd(dT, left), _NN), bothT, _NN)
    dA = jnp.where(strict, dA, 0.0)
    E = dP * P + dA * A
    dS = jnp.concatenate([_bd(-dA * decay, left), _bd(dP * decay, left)],
                         axis=0)
    back = _dot(dS, k, _NN)                     # d(beta K), dQ by the scores
    dkb = back[:2 * C] + dbkg * eg
    kdk = dKd * k * ekd
    dk = _dot(dS, jnp.concatenate([kb, q], axis=0), _TN) + dkb * bc \
        + dKd * ekd

    def rows(x):
        return jnp.sum(x, axis=1, keepdims=True)

    dgam = rows(dbkg * bkg + dQg * q * eg - kdk) + jnp.concatenate(
        [rows(jnp.where(left, E, 0.0)), rows(jnp.where(left, 0.0, E))],
        axis=0)
    dbeta = rows(dkb * k) + rows(dbv * v)
    lanes = _iota((1, 2 * C), 1)
    through_last = sum(
        jnp.where(lanes == at + C - 1,
                  rows(jnp.sum(kdk[at:at + C], axis=0, keepdims=True)), 0.0)
        for at in (0, C))
    return (back[2 * C:] + dQg * eg, dk, dbv * bc, dgam, dbeta,
            through_last - jnp.sum(E, axis=0, keepdims=True))


def _prepare_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, u_ref, w_ref,
                        qg_ref, kd_ref, p_ref, a_ref, t_ref):
    C = t_ref.shape[2]
    masks = _pair_masks(C)
    gam, last, cols = _pair_rows(g_ref[0], b_ref[0])
    a_ref[0] = jnp.exp(last)
    for n in range(q_ref.shape[1]):
        U, W, Qg, Kd, P, T = _pair_fwd(
            q_ref[0, n], k_ref[0, n], v_ref[0, n], gam[n:n + 1],
            *_pair_cols(cols, n, masks[0]), masks)
        u_ref[0, n], w_ref[0, n], qg_ref[0, n], kd_ref[0, n] = U, W, Qg, Kd
        t_ref[0, n] = T
        p_ref[0, n, 0] = P[:, :C]
        p_ref[0, n, 1] = P[:, C:]


def _prepare_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, du_ref,
                        dw_ref, dqg_ref, dkd_ref, dp_ref, da_ref,
                        dq_ref, dk_ref, dv_ref, dg_ref, db_ref, *, rep):
    """dq and dk are a key head's: its rep value heads' steps follow each
    other on the grid's second axis and add into one block."""
    C = t_ref.shape[2]
    masks = _pair_masks(C)
    gam, last, cols = _pair_rows(g_ref[0], b_ref[0])
    lanes, pair = _iota((2 * C, 2 * C), 1), _iota((_ROWS, 2 * C), 0)
    down = jnp.zeros((2 * C, 2 * C), F32)       # columns, a pair a lane
    along = da_ref[0] * jnp.exp(last)           # rows: da a, at last
    for n in range(q_ref.shape[1]):
        dP = jnp.concatenate([dp_ref[0, n, 0], dp_ref[0, n, 1]], axis=1)
        dq, dk, dv, dgam, dbeta, row = _pair_bwd(
            q_ref[0, n], k_ref[0, n], v_ref[0, n], gam[n:n + 1],
            *_pair_cols(cols, n, masks[0]), t_ref[0, n], du_ref[0, n],
            dw_ref[0, n], dqg_ref[0, n], dkd_ref[0, n], dP, masks)
        if rep > 1:
            first = pl.program_id(1) == 0
            dq = jnp.where(first, dq, dq_ref[0, n] + dq)
            dk = jnp.where(first, dk, dk_ref[0, n] + dk)
        dq_ref[0, n], dk_ref[0, n], dv_ref[0, n] = dq, dk, dv
        down = jnp.where(lanes == n, dgam, down)
        down = jnp.where(lanes == _ROWS + n, dbeta, down)
        along = along + jnp.where(pair == n, row, 0.0)
    across = down.T
    db_ref[0] = across[_ROWS:2 * _ROWS]
    # dg_m = sum of dgam_i over the chunk's i >= m
    same, r, c = _same_half(C)
    dg_ref[0] = _dot(across[:_ROWS] + along,
                     jnp.where(same & (r >= c), 1.0, 0.0), _NN)


def _in_pairs(x):
    """[B, nc, CHUNK, ...] or rows [B, nc, CHUNK] -> the chunks in steps of
    PAIRS pairs [steps, PAIRS, 2 CHUNK, ...] (rows: [steps, _ROWS,
    2 CHUNK]), zero chunks appended to a whole step."""
    n = x.shape[0] * x.shape[1]
    x = x.reshape((n,) + x.shape[2:])
    x = jnp.pad(x, ((0, -n % (2 * PAIRS)),) + ((0, 0),) * (x.ndim - 1))
    x = x.reshape((-1, PAIRS, 2 * CHUNK) + x.shape[2:])
    if x.ndim == 3:
        x = jnp.pad(x, ((0, 0), (0, _ROWS - PAIRS), (0, 0)))
    return x


def _from_pairs(x, B, nc, *chunk):
    """`_in_pairs` undone, for what a kernel wrote by pairs: -> [B, nc,
    *chunk], a chunk's own shape given."""
    if x.ndim == 3:
        x = x[:, :PAIRS]
    return x.reshape((-1,) + chunk)[:B * nc].reshape((B, nc) + chunk)


def _key_heads(q, k, B, nc):
    """-> (q, k, rep, the grid steps a head): q and k as the kernels'
    grid reads them. Where a head's chunks fill whole steps, a value
    head's step reads its key head's block and q and k stay at their Bk
    = B / rep heads; else they are copied a value head (rep 1, the grid
    over all chunks in a row)."""
    rep = B // q.shape[0]
    if rep > 1 and nc % (2 * PAIRS):
        q, k = _at_value_heads(q, k, B)
        rep = 1
    return q, k, rep, nc // (2 * PAIRS) if rep > 1 else 1


def _prepare_specs(dk, dv, rep, per_head):
    """The grid is (a key head's steps, its rep value heads): step
    (i, r) holds the chunks of value head `i // per_head * rep + r` that
    key step i holds of its head, so q's and k's block stays where it is
    while r runs. With rep 1 a value step is step i."""
    def at(*block, key=False):
        zeros = (0,) * len(block)
        if key:
            return pl.BlockSpec((1,) + block, lambda i, r: (i,) + zeros)
        return pl.BlockSpec((1,) + block, lambda i, r: (
            (i // per_head * rep + r) * per_head + i % per_head,) + zeros)
    C = CHUNK
    return dict(k=at(PAIRS, 2 * C, dk), v=at(PAIRS, 2 * C, dv),
                row=at(_ROWS, 2 * C), p=at(PAIRS, 2, C, C),
                t=at(PAIRS, C, 2 * C), key=at(PAIRS, 2 * C, dk, key=True))


def _prepare_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames="interpret")
@_pf.trace_timed_call("gdn_prepare_fwd")
def _prepare_fwd_pallas(q, k, v, g, beta, interpret=False):
    """`_prepare` as a kernel: the same operands -> (U, W, Qg, Kd, P, a,
    T in pairs [steps, PAIRS, C, 2C]: the backward kernel's residual).
    Under `jax.jit`, as the backward's wrapper is, so that a step's
    layers and passes trace and lower the kernel once."""
    B, nc, C, dv = v.shape
    q, k, rep, per_head = _key_heads(q, k, B, nc)
    dk = q.shape[-1]
    ins = [_in_pairs(x) for x in (q, k, v, g, beta)]
    steps = ins[2].shape[0]
    sp = _prepare_specs(dk, dv, rep, per_head)
    like = jax.ShapeDtypeStruct
    wide_k, wide_v = (steps,) + ins[0].shape[1:], ins[2].shape
    U, W, Qg, Kd, P, a, T = pl.pallas_call(
        _prepare_fwd_kernel,
        grid=(steps // rep, rep),
        in_specs=[sp["key"], sp["key"], sp["v"], sp["row"], sp["row"]],
        out_specs=[sp["v"], sp["k"], sp["k"], sp["k"], sp["p"], sp["row"],
                   sp["t"]],
        out_shape=[like(wide_v, F32), like(wide_k, F32), like(wide_k, F32),
                   like(wide_k, F32), like((steps, PAIRS, 2, C, C), F32),
                   like(ins[3].shape, F32),
                   like((steps, PAIRS, C, 2 * C), F32)],
        compiler_params=_prepare_params(),
        interpret=interpret,
        name="gdn_prepare_fwd",
    )(*ins)
    return (_from_pairs(U, B, nc, C, dv),
            *(_from_pairs(x, B, nc, C, dk) for x in (W, Qg, Kd)),
            _from_pairs(P, B, nc, C, C), _from_pairs(a, B, nc, C)[..., -1],
            T)


@functools.partial(jax.jit, static_argnames="interpret")
@_pf.trace_timed_call("gdn_prepare_bwd")
def _prepare_bwd_pallas(q, k, v, g, beta, T, grads, interpret=False):
    """`_prepare`'s transpose as a kernel: its operands, the forward
    kernel's T and the gradients to U, W, Qg, Kd, P [B, nc, ...] and a
    [B, nc] -> dq, dk, dv, dg, dbeta like the operands: dq and dk at q's
    heads, a key head's value heads summed."""
    B, nc, C, dv = v.shape
    Bk = q.shape[0]
    q, k, rep, per_head = _key_heads(q, k, B, nc)
    dk = q.shape[-1]
    dU, dW, dQg, dKd, dP, da = grads
    # da beside the chunk's last token, where `last` is read
    da = da[..., None] * (jnp.arange(C) == C - 1)
    ins = [_in_pairs(x) for x in (q, k, v, g, beta)]
    ins += [T] + [_in_pairs(x) for x in (dU, dW, dQg, dKd)]
    steps = T.shape[0]
    ins += [_in_pairs(dP).reshape((steps, PAIRS, 2, C, C)), _in_pairs(da)]
    sp = _prepare_specs(dk, dv, rep, per_head)
    like = jax.ShapeDtypeStruct
    wide_k, wide_v, row = ins[0].shape, ins[2].shape, ins[3].shape
    out = pl.pallas_call(
        functools.partial(_prepare_bwd_kernel, rep=rep),
        grid=(steps // rep, rep),
        in_specs=[sp["key"], sp["key"], sp["v"], sp["row"], sp["row"],
                  sp["t"], sp["v"], sp["k"], sp["k"], sp["k"], sp["p"],
                  sp["row"]],
        out_specs=[sp["key"], sp["key"], sp["v"], sp["row"], sp["row"]],
        out_shape=[like(wide_k, F32), like(wide_k, F32), like(wide_v, F32),
                   like(row, F32), like(row, F32)],
        compiler_params=_prepare_params(),
        interpret=interpret,
        name="gdn_prepare_bwd",
    )(*ins)
    heads = q.shape[0]
    to_keys = [_from_pairs(x, heads, nc, C, dk) for x in out[:2]]
    if heads != Bk:     # copied a value head: back to the key heads
        to_keys = [x.reshape((Bk, -1) + x.shape[1:]).sum(1) for x in to_keys]
    return (*to_keys, _from_pairs(out[2], B, nc, C, dv),
            *(_from_pairs(x, B, nc, C) for x in out[3:]))


# ======================= dispatch =======================

def state_path() -> str:
    """What the state pass of a program traced now runs as: `pallas` |
    `lax.scan`."""
    return "pallas" if _pallas_available() else "lax.scan"


def prepare_path() -> str:
    """What the chunk preparation of a program traced now runs as:
    `pallas` (`gdn_prepare_fwd`, `gdn_prepare_bwd`) | `xla` (`prepare`
    under autodiff). The CPU tests also give `interpret`: the kernels in
    Pallas's interpreter."""
    return "pallas" if _pallas_available() else "xla"


def gated_delta_rule(q, k, v, g, beta):
    """o [b, s, H, dv] in v's type from q, k [b, s, Hk, dk], v
    [b, s, H, dv] and g (log decay, <= 0), beta [b, s, H];
    differentiable in all five. q and k come a value head (Hk = H) or a
    key head, Hk dividing H: key head h serves value heads h H / Hk and
    those that follow, and on the kernels its q and k are read from
    where they lie, never copied a value head. The state and every
    product in float32. A row that is no whole number of chunks is
    padded to one: a padded token has beta = 0 and g = 0, so the state
    passes through it."""
    b, s = v.shape[:2]
    pad = -s % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    O = _rule(*(_heads_first(x) for x in (q, k, v, g, beta)))
    return _tokens_first(O, b)[:, :s]


def gated_delta_rule_heads_first(q, k, v, g, beta):
    """`gated_delta_rule` on q, k [b, Hk, sp, dk] and v [b, H, sp, dv]
    that lie heads first already, as `gdn_operands.py` writes them: sp
    is s up to whole chunks, the rows beyond s zeros. g and beta
    [b, s, H] and o [b, s, H, dv] as ever. Nothing is turned or cast on
    the way to the kernels but the two rows a head."""
    b, s, _H = g.shape
    sp = v.shape[2]
    g, beta = (_heads_first(jnp.pad(x, ((0, 0), (0, sp - s), (0, 0))))
               for x in (g, beta))
    O = _rule(*(x.reshape((-1, sp // CHUNK, CHUNK, x.shape[-1]))
                for x in (q, k, v)), g, beta)
    return _tokens_first(O, b)[:, :s]


@jax.custom_vjp
def _rule(q, k, v, g, beta):
    """The rule on chunks, heads first: q, k [Bk, nc, C, dk], v
    [B, nc, C, dv], g, beta [B, nc, C] -> O [B, nc, C, dv] in v's
    type."""
    return _rule_fwd(q, k, v, g, beta)[0]


def _f32(xs):
    return tuple(x.astype(F32) for x in xs)


def _rule_fwd(q, k, v, g, beta):
    ins, mode = (q, k, v, g, beta), prepare_path()
    with jax.named_scope("prepare"):
        if mode == "xla":
            made = prepare(*_f32(ins))
        else:
            *made, _T = _prepare_fwd_pallas(
                *_f32(ins), interpret=mode == "interpret")
    O, res = _state_vjp_fwd(*made, state_path())
    return O.astype(v.dtype), (ins, res[-1])


def _rule_bwd(res, do):
    """What the chunk preparation makes for the state pass (U, W, Qg, Kd,
    P: 1.2 GB a layer at 16,384 tokens and 32 heads, and on the kernels
    the chunks' T, 134 MB) is made again here and lives through this
    function alone, not from the forward on. The states entering the
    chunks are the forward kernel's.

    The kernels' forward call stands behind a barrier with dO. Without
    it XLA finds that a recomputed forward (`jax.checkpoint`) has just
    made the same and runs the kernel once for both: a call saved, and
    its 1.2 GB alive across everything between that forward and this
    backward (the step's reported peak 16.89 GB for 15.73 at
    `qwen3-next-80b-l4-e64`, PERF.md section 6, PR 40)."""
    ins, states = res
    dO = do.astype(F32)
    mode = prepare_path()
    with jax.named_scope("prepare"):
        if mode == "xla":
            made, back = jax.vjp(prepare, *_f32(ins))
        else:
            *made, T = _prepare_fwd_pallas(
                *jax.lax.optimization_barrier((_f32(ins), dO))[0],
                interpret=mode == "interpret")
    grads = _state_vjp_bwd(state_path(), (*made, states), dO)
    with jax.named_scope("prepare"):
        grads = back(grads) if mode == "xla" else _prepare_bwd_pallas(
            *_f32(ins), T, grads, interpret=mode == "interpret")
    return tuple(d.astype(x.dtype) for d, x in zip(grads, ins))


_rule.defvjp(_rule_fwd, _rule_bwd)
