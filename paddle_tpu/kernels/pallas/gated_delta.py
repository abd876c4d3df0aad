"""The gated delta rule in chunks, and its sequential pass as Pallas TPU
kernels, forward and backward.

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

`prepare` computes the first block for every chunk of `CHUNK` tokens at
once (XLA, under autodiff, scoped `prepare`; float32, the products at
`highest` precision). Only the three lines with S are sequential: the
state pass (`_state_vjp_fwd`, `_state_vjp_bwd`). `gated_delta_rule` is
one `jax.custom_vjp` over both.

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

A block holds `HEADS` heads whose chains are independent: the scheduler
overlaps one head's products with another's. On a TPU backend the
kernels are the only path (`state_path`). Elsewhere (the CPU tests) the
same three lines and the same backward formulas run as a `lax.scan`
over chunks.
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


def prepare(q, k, v, g, beta):
    """q, k [b, s, H, dk], v [b, s, H, dv], g, beta [b, s, H], float32 ->
    (U, W, Qg, Kd, P, a) as the state pass takes them, B = b * H and
    nc = s / CHUNK."""
    b, s, H, _dk = q.shape
    chunk, nc = CHUNK, s // CHUNK

    def heads_first(x):     # [b, s, H, ...] -> [b * H, nc, chunk, ...]
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape((b * H, nc, chunk) + x.shape[3:])

    q, k, v, g, beta = (heads_first(x) for x in (q, k, v, g, beta))
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
    return U, W, q * e_gam, Kd, P, jnp.exp(last)


# ======================= dispatch =======================

def state_path() -> str:
    """What the state pass of a program traced now runs as: `pallas` |
    `lax.scan`."""
    return "pallas" if _pallas_available() else "lax.scan"


def gated_delta_rule(q, k, v, g, beta):
    """o [b, s, H, dv] in v's type from q, k [b, s, H, dk], v
    [b, s, H, dv] and g (log decay, <= 0), beta [b, s, H];
    differentiable in all five. The state and every product in float32.
    A row that is no whole number of chunks is padded to one: a padded
    token has beta = 0 and g = 0, so the state passes through it."""
    s = v.shape[1]
    pad = -s % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    return _rule(q, k, v, g, beta)[:, :s]


@jax.custom_vjp
def _rule(q, k, v, g, beta):
    return _rule_fwd(q, k, v, g, beta)[0]


def _f32(xs):
    return tuple(x.astype(F32) for x in xs)


def _tokens_first(O, b, H):
    """[b * H, nc, C, dv] -> [b, s, H, dv]."""
    return jnp.moveaxis(O.reshape((b, H, -1, O.shape[-1])), 1, 2)


def _rule_fwd(q, k, v, g, beta):
    ins = (q, k, v, g, beta)
    with jax.named_scope("prepare"):
        made = prepare(*_f32(ins))
    O, res = _state_vjp_fwd(*made, state_path())
    b, _s, H, _dv = v.shape
    return _tokens_first(O, b, H).astype(v.dtype), (ins, res[-1])


def _rule_bwd(res, do):
    """The chunk preparation is made again here and differentiated:
    what it makes (some 3.5 GB a layer at 16,384 tokens and 32 heads)
    lives through this function alone, not from the forward on. The
    states entering the chunks are the forward kernel's."""
    ins, states = res
    b, s, H, dv = do.shape
    with jax.named_scope("prepare"):
        made, back = jax.vjp(prepare, *_f32(ins))
    dO = jnp.moveaxis(do.astype(F32), 2, 1).reshape(
        (b * H, s // CHUNK, CHUNK, dv))
    grads = _state_vjp_bwd(state_path(), (*made, states), dO)
    with jax.named_scope("prepare"):
        grads = back(grads)
    return tuple(d.astype(x.dtype) for d, x in zip(grads, ins))


_rule.defvjp(_rule_fwd, _rule_bwd)
