"""Pallas TPU selective scan (the Mamba-1 recurrence), forward and backward.

    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * x_t) (x) B_t,   h_0 = 0
    y_t = h_t . C_t + D * x_t

x, delta, y: [b, L, E]; A: [E, N]; B, C: [b, L, N]; D: [E]. The state
h [N, E] is float32 whatever the operands' types, and nothing of size
L x E x N reaches HBM in either direction (Gu & Dao 2023, section 3.3):

* forward (`ssm_scan_fwd`): the grid walks time in chunks of `CHUNK`
  steps and, inside a chunk, the channels in tiles of `TILE`; a tile's
  state [N, TILE] (N in sublanes, channels in lanes) lives in VMEM across
  the whole walk, and only its value at each chunk's start is written
  out ([b, L/CHUNK, N, E] float32, 1/CHUNK of the state sequence).
* backward (`ssm_scan_bwd`): the same walk from the last chunk to the
  first. A chunk's states are made again in VMEM from the saved start,
  then the adjoint g_t = C_t dy_t + a_{t+1} g_{t+1} runs down the chunk
  and every operand's gradient is read off it. dA and dD leave as one
  partial a chunk, dB and dC as partials over the 128 lanes (summed over
  the channel tiles in VMEM); XLA finishes those sums.

B_t and C_t vary along the state axis, which is the sublane axis here:
the kernels are handed them already spread over a vreg's 128 lanes
([b, L, N, 128] float32, 1/40 of the state sequence at E = 5120), so a
step needs no lane broadcast. Each step is a handful of vector operations
on [N, TILE] and two on the exponential unit; the matrix unit is idle.

`selective_scan` is the one entry. On a TPU backend the kernels are the
only path: an operand they cannot take raises. Elsewhere (CPU tests) the
same chunked algorithm runs as `lax.scan` over chunks with the same saved
starts, the backward being jax's own transposition of one chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from ...observability import perf as _pf

from .flash_attention import _pallas_available

CHUNK = 64          # time steps between saved states
TILE = 512          # channels a kernel instance holds (4 vregs wide)
_LANES = 128
_UNROLL = 8
_VMEM_LIMIT = 64 * 1024 * 1024
F32 = jnp.float32


def _wide(tile, reps):
    """[N, 128] -> [N, 128 * reps]: the same vregs, named again."""
    return tile if reps == 1 else jnp.tile(tile, (1, reps))


def _fold(x, reps):
    """[N, 128 * reps] -> [N, 128]: lane groups summed, lanes kept."""
    out = x[:, :_LANES]
    for r in range(1, reps):
        out = out + x[:, r * _LANES:(r + 1) * _LANES]
    return out


def _row(ref, t):
    return ref[pl.ds(t, 1), :]


def _walk(T, step, carry):
    """`step(t, carry)` for t = 0 .. T-1, `_UNROLL` steps to a loop
    iteration written out in line (Mosaic unrolls a loop whole or not
    at all), so that a step's loads and exponentials overlap its
    neighbours' arithmetic."""
    def group(k, carry):
        base = pl.multiple_of(k * _UNROLL, _UNROLL)
        for i in range(_UNROLL):
            carry = step(base + i, carry)
        return carry
    return jax.lax.fori_loop(0, T // _UNROLL, group, carry)


# ======================= forward =======================

def _fwd_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, dv_ref, y_ref, hs_ref,
                h_sc, u_sc, y_sc, *, T, reps):
    j, e = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _start():
        h_sc[e] = jnp.zeros(h_sc.shape[1:], F32)

    h0 = h_sc[e]
    hs_ref[0, 0] = h0
    xf = x_ref[0].astype(F32)
    u_sc[...] = xf * d_ref[0]
    A = a_ref[...]

    def step(t, h):
        a = jnp.exp(d_ref[0, pl.ds(t, 1), :] * A)
        h = a * h + _wide(b_ref[0, t], reps) * _row(u_sc, t)
        y_sc[pl.ds(t, 1), :] = jnp.sum(h * _wide(c_ref[0, t], reps),
                                       axis=0, keepdims=True)
        return h

    h_sc[e] = _walk(T, step, h0)
    y_ref[0] = (y_sc[...] + dv_ref[...] * xf).astype(y_ref.dtype)


def _blocks(T, tE, N, nc, reverse):
    """BlockSpecs by role for the grid (batch, chunk, tile); `reverse`
    walks the chunks from the last to the first."""
    def jj(j):
        return nc - 1 - j if reverse else j

    return dict(
        seq=pl.BlockSpec((1, T, tE), lambda b, j, e: (b, jj(j), e)),
        mat=pl.BlockSpec((N, tE), lambda b, j, e: (0, e)),
        vec=pl.BlockSpec((1, tE), lambda b, j, e: (0, e)),
        wide=pl.BlockSpec((1, T, N, _LANES),
                          lambda b, j, e: (b, jj(j), 0, 0)),
        state=pl.BlockSpec((1, 1, N, tE),
                           lambda b, j, e: (b, jj(j), 0, e)),
        chunk_vec=pl.BlockSpec((1, 1, 1, tE),
                               lambda b, j, e: (b, jj(j), 0, e)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


@_pf.trace_timed_call("ssm_scan_fwd")
def _scan_fwd_pallas(x, delta, At, Bw, Cw, Dv, T, tE, interpret=False):
    """x [b, L, E]; delta [b, L, E] f32; At [N, E]; Bw, Cw [b, L, N, 128];
    Dv [1, E]. L % T == 0, E % tE == 0. -> (y like x, starts
    [b, L/T, N, E] f32)."""
    b, L, E = x.shape
    N = At.shape[0]
    nc, nE = L // T, E // tE
    s = _blocks(T, tE, N, nc, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, T=T, reps=tE // _LANES),
        grid=(b, nc, nE),
        in_specs=[s["seq"], s["seq"], s["mat"], s["wide"], s["wide"],
                  s["vec"]],
        out_specs=[s["seq"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct((b, L, E), x.dtype),
                   jax.ShapeDtypeStruct((b, nc, N, E), F32)],
        scratch_shapes=[pltpu.VMEM((nE, N, tE), F32),
                        pltpu.VMEM((T, tE), F32),
                        pltpu.VMEM((T, tE), F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssm_scan_fwd",    # also the innermost jax.named_scope
    )(x, delta, At, Bw, Cw, Dv)


# ======================= backward =======================

def _bwd_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, dv_ref, hs_ref, dy_ref,
                dx_ref, dd_ref, da_ref, db_ref, dc_ref, ddv_ref,
                g_sc, hh_sc, u_sc, dy_sc, gb_sc, s1_sc, *, T, reps):
    j, e = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)            # the last chunk: nothing follows it
    def _start():
        g_sc[e] = jnp.zeros(g_sc.shape[1:], F32)

    @pl.when(e == 0)            # dB, dC: summed over the channel tiles
    def _zero():
        db_ref[...] = jnp.zeros(db_ref.shape, F32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, F32)

    xf = x_ref[0].astype(F32)
    dl = d_ref[0]
    dyf = dy_ref[0].astype(F32)
    u_sc[...] = xf * dl
    dy_sc[...] = dyf
    A = a_ref[...]

    # the chunk's states again, from its saved start: hh[t + 1] = h_t
    hh_sc[0] = hs_ref[0, 0]

    def again(t, h):
        a = jnp.exp(d_ref[0, pl.ds(t, 1), :] * A)
        h = a * h + _wide(b_ref[0, t], reps) * _row(u_sc, t)
        hh_sc[t + 1] = h
        return h

    _walk(T, again, hh_sc[0])

    def down(i, carry):
        g, dA = carry           # g: a_{t+1} * (adjoint of h_{t+1})
        t = T - 1 - i
        d_row, dy_row = d_ref[0, pl.ds(t, 1), :], _row(dy_sc, t)
        bt, ct = _wide(b_ref[0, t], reps), _wide(c_ref[0, t], reps)
        g = g + ct * dy_row                 # the adjoint of h_t
        dc_ref[0, t] += _fold(hh_sc[t + 1] * dy_row, reps)
        db_ref[0, t] += _fold(g * _row(u_sc, t), reps)
        gb_sc[pl.ds(t, 1), :] = jnp.sum(g * bt, axis=0, keepdims=True)
        a = jnp.exp(d_row * A)
        w = g * hh_sc[t] * a                # (adjoint of a_t) * a_t
        s1_sc[pl.ds(t, 1), :] = jnp.sum(w * A, axis=0, keepdims=True)
        return a * g, dA + w * d_row

    g, dA = _walk(T, down, (g_sc[e], jnp.zeros_like(A)))
    g_sc[e] = g
    da_ref[0, 0] = dA
    gb = gb_sc[...]             # the adjoint of u = delta * x
    dx_ref[0] = (gb * dl + dv_ref[...] * dyf).astype(dx_ref.dtype)
    dd_ref[0] = s1_sc[...] + gb * xf
    ddv_ref[0, 0] = jnp.sum(dyf * xf, axis=0, keepdims=True)


@_pf.trace_timed_call("ssm_scan_bwd")
def _scan_bwd_pallas(x, delta, At, Bw, Cw, Dv, starts, dy, T, tE,
                     interpret=False):
    """-> dx like x, ddelta f32, dA partials [b, L/T, N, E], dB and dC
    lane partials [b, L, N, 128], dD partials [b, L/T, 1, E]."""
    b, L, E = x.shape
    N = At.shape[0]
    nc, nE = L // T, E // tE
    s = _blocks(T, tE, N, nc, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, T=T, reps=tE // _LANES),
        grid=(b, nc, nE),
        in_specs=[s["seq"], s["seq"], s["mat"], s["wide"], s["wide"],
                  s["vec"], s["state"], s["seq"]],
        out_specs=[s["seq"], s["seq"], s["state"], s["wide"], s["wide"],
                   s["chunk_vec"]],
        out_shape=[jax.ShapeDtypeStruct((b, L, E), x.dtype),
                   jax.ShapeDtypeStruct((b, L, E), F32),
                   jax.ShapeDtypeStruct((b, nc, N, E), F32),
                   jax.ShapeDtypeStruct((b, L, N, _LANES), F32),
                   jax.ShapeDtypeStruct((b, L, N, _LANES), F32),
                   jax.ShapeDtypeStruct((b, nc, 1, E), F32)],
        scratch_shapes=[pltpu.VMEM((nE, N, tE), F32),
                        pltpu.VMEM((T + 1, N, tE), F32),
                        pltpu.VMEM((T, tE), F32), pltpu.VMEM((T, tE), F32),
                        pltpu.VMEM((T, tE), F32), pltpu.VMEM((T, tE), F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssm_scan_bwd",
    )(x, delta, At, Bw, Cw, Dv, starts, dy)


# ======================= the same, without a kernel =======================

def _chunk_xla(h, x, delta, A, B, C, D):
    """One chunk, one step at a time. h [b, E, N] f32; x, delta
    [T, b, E]; B, C [T, b, N] -> (h at the end, y [T, b, E] f32)."""
    def step(h, inp):
        x_t, d_t, b_t, c_t = inp
        h = jnp.exp(d_t[..., None] * A) * h \
            + (d_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + D * x_t
    return jax.lax.scan(step, h, (x, delta, B, C))


def _chunked(a, T):
    """[b, L, ...] -> [L/T, T, b, ...]."""
    b, L = a.shape[:2]
    a = jnp.moveaxis(a, 0, 1).reshape((L // T, T, b) + a.shape[2:])
    return a


def _unchunked(a):
    nc, T, b = a.shape[:3]
    return jnp.moveaxis(a.reshape((nc * T, b) + a.shape[3:]), 0, 1)


def _scan_fwd_xla(x, delta, A, B, C, D, T):
    b, _L, E = x.shape
    xs = tuple(_chunked(a.astype(F32), T) for a in (x, delta, B, C))

    def chunk(h, inp):
        h2, y = _chunk_xla(h, *inp[:2], A, *inp[2:], D)
        return h2, (y, h)

    _h, (y, starts) = jax.lax.scan(
        chunk, jnp.zeros((b, E, A.shape[1]), F32), xs)
    return _unchunked(y).astype(x.dtype), starts     # [L/T, b, E, N]


def _scan_bwd_xla(x, delta, A, B, C, D, starts, dy, T):
    xs = tuple(_chunked(a.astype(F32), T) for a in (x, delta, B, C, dy))

    def chunk(carry, inp):
        g, dA, dD = carry
        h0, x_c, d_c, b_c, c_c, dy_c = inp
        _out, back = jax.vjp(_chunk_xla, h0, x_c, d_c, A, b_c, c_c, D)
        g, dx, dd, dA_c, dB, dC, dD_c = back((g, dy_c))
        return (g, dA + dA_c, dD + dD_c), (dx, dd, dB, dC)

    zero = (jnp.zeros_like(starts[0]), jnp.zeros_like(A), jnp.zeros_like(D))
    (_g, dA, dD), parts = jax.lax.scan(chunk, zero, (starts,) + xs,
                                       reverse=True)
    dx, dd, dB, dC = (_unchunked(p) for p in parts)
    return dx, dd, dA, dB, dC, dD


# ======================= dispatch =======================

def _tile(E):
    """Channels a kernel instance holds, or the reason the kernels
    cannot take this width."""
    if E % _LANES:
        raise ValueError(
            f"selective_scan: {E} channels are no multiple of {_LANES}; "
            "the TPU kernel tiles them over a vreg's lanes")
    return next(t for t in (TILE, 384, 256, _LANES) if E % t == 0)


def scan_path(L, E):
    """What `selective_scan` will run for these sizes on this backend:
    `pallas, chunk T, tile E` | `xla`."""
    if not _pallas_available():
        return "xla"
    return f"pallas, chunk {CHUNK}, tile {_tile(E)}"


def _pad_time(a, dtype=None):
    """Time padded to whole chunks. A padded step has delta = 0 and
    x = 0: the state passes through it, and so does its adjoint."""
    pad = -a.shape[1] % CHUNK
    a = a if dtype is None else a.astype(dtype)
    return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) \
        if pad else a


def _spread(a):
    """[b, L, N] -> [b, L, N, 128] float32."""
    return jnp.broadcast_to(a[..., None], a.shape + (_LANES,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, delta, A, B, C, D, mode):
    return _scan_vjp_fwd(x, delta, A, B, C, D, mode)[0]


def _scan_vjp_fwd(x, delta, A, B, C, D, mode):
    L = x.shape[1]
    xp, dp = _pad_time(x), _pad_time(delta, F32)
    Bp, Cp = _pad_time(B, F32), _pad_time(C, F32)
    A32, D32 = A.astype(F32), D.astype(F32)
    if mode == "xla":
        y, starts = _scan_fwd_xla(xp, dp, A32, Bp, Cp, D32, CHUNK)
    else:
        y, starts = _scan_fwd_pallas(
            xp, dp, A32.T, _spread(Bp), _spread(Cp), D32[None], CHUNK,
            _tile(x.shape[2]), interpret=mode == "interpret")
    return y[:, :L], (x, delta, A, B, C, D, starts)


def _scan_vjp_bwd(mode, res, dy):
    x, delta, A, B, C, D, starts = res
    L = x.shape[1]
    xp, dp, dyp = _pad_time(x), _pad_time(delta, F32), _pad_time(dy)
    Bp, Cp = _pad_time(B, F32), _pad_time(C, F32)
    A32, D32 = A.astype(F32), D.astype(F32)
    if mode == "xla":
        dx, dd, dA, dB, dC, dD = _scan_bwd_xla(
            xp, dp, A32, Bp, Cp, D32, starts, dyp, CHUNK)
    else:
        dx, dd, dA, dB, dC, dD = _scan_bwd_pallas(
            xp, dp, A32.T, _spread(Bp), _spread(Cp), D32[None], starts,
            dyp, CHUNK, _tile(x.shape[2]), interpret=mode == "interpret")
        dA = jnp.sum(dA, axis=(0, 1)).T
        dB, dC = jnp.sum(dB, axis=-1), jnp.sum(dC, axis=-1)
        dD = jnp.sum(dD, axis=(0, 1, 2))
    return (dx[:, :L].astype(x.dtype), dd[:, :L].astype(delta.dtype),
            dA.astype(A.dtype), dB[:, :L].astype(B.dtype),
            dC[:, :L].astype(C.dtype), dD.astype(D.dtype))


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def selective_scan(x, delta, A, B, C, D, interpret=False):
    """y [b, L, E] in x's type from x, delta [b, L, E], A [E, N], B, C
    [b, L, N], D [E]; differentiable in all six. Neither the softplus
    that makes delta nor the gate `* silu(z)` that follows is fused in:
    both are elementwise passes XLA fuses with their neighbours.
    `interpret` runs the kernels in Pallas's interpreter (CPU tests)."""
    mode = "interpret" if interpret else (
        "pallas" if _pallas_available() else "xla")
    return _scan(x, delta, A, B, C, D, mode)
