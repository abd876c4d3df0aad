"""Pallas TPU kernels `cca_mix_fwd` and `cca_mix_bwd`: what a compressed
convolutional attention layer (nn/layers/cca.py) does to its latent
between `qkv_proj` and the rotary, in one pass each way.

`qkv` [b, s, (H + 2 Hk) d] is the projection's output as it stands:
H query heads of d lanes, Hk = 2 key heads, two value heads. With
z = q~ | k~ (the first n = (H + Hk) d lanes), G = H / Hk and a head's
lanes written [h]:

    u_t     = a_0 z_{t-1} + a_1 z_t + b           z1 = u in qkv's type
    z2_t[h] = z1_{t-1}[h] A_0[h] + z1_t[h] A_1[h] + b'[h]
    m_h     = (z[h] + z[H + h // G]) / 2,  mbar_g = mean of g's m_h
    q_h     = sqrt(d) unit(z2[h] + m_h)
    k_g     = tau_g sqrt(d) unit(z2[H + g] + mbar_g)
    v       = (v1_t, v2_{t-1})

with z, z1 and v2 zero before a row's start. Out come q [b, s, H d],
k and v [b, s, Hk d] in qkv's type: rows, as the rotary and the flash
kernels read them. Everything between is float32 in VMEM but the two
products a head, whose operands are qkv's type and whose sums are
float32 (`ops.cca_mix`'s chain, ops/cca_ops.py, is the same arithmetic
as a dozen XLA passes and rounds z2 to qkv's type besides).

A grid step holds `ROWS` rows of all lanes; it walks its two key heads
and a key head its G query heads, each one loop body whose lanes are a
dynamic slice of the block (nothing is unrolled by head: the kernels'
trace and lowering are set-up time of every run) and whose rows are the
block's at once (walking a block in four chunks of 128 rows took 0.72
and 1.46 ms a call on the chip where this takes 0.46 and 0.82:
PERF.md, PR 44). A
head is worked as a window that begins `HALO` rows before the block
(and, backward, ends `HALO` rows after it): a tap's neighbour is then a
sublane roll of the window, whose wrapped rows lie in the margin nobody
reads. The rows before a block come through a second block of `HALO`
rows of the same array, the rows after it through a third.

The backward makes u, z1, z2 and the norms again from `qkv` (the only
residual besides the parameters) and goes back through them, for a
head with w = z2 + m, r = rsqrt(|w|^2), c = sqrt(d) (times tau_g for a
key head) and g the gradient of q_h or k_g:

    dw = c r (g - w r^2 <g, w>),    dtau_g = sum_t <g, sqrt(d) r w>
    du_t = dw_t A_1^T + dw_{t+1} A_0^T            (dw in qkv's type)
    dz_t = a_1 du_t + a_0 du_{t+1} + the mean's share of the dw
    dA_0 = sum_t z1_t^T dw_{t+1},  dA_1 = sum_t z1_t^T dw_t
    da_0 = sum_t du_t z_{t-1},  da_1 = sum_t du_t z_t,  db = sum_t du_t

The gradient of `qkv` is written once, all lanes, in its type; the
parameters' gradients are float32 sums in blocks that stay in VMEM
along the row axis (dA [H + Hk, d, 2 d] as dA_0 | dA_1; `sums`
[5, 8, n]: da_0, da_1, db, db', and dtau a lane of the key heads, each
eight partial rows that XLA adds).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability import perf as _pf

ROWS = 512          # rows a grid step holds
HALO = 16           # rows of a neighbour's block: a whole bfloat16 tile
TILE = 8            # rows of a float32 tile: a partial sum's rows
F32 = jnp.float32
_VMEM_LIMIT = 64 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))      # x @ y^T
_TN = (((0,), (0,)), ((), ()))      # x^T @ y


def reject_reason(qkv_shape, qkv_dtype, heads, kv_heads):
    """Why the kernels do not take this call (None: they do). They take
    a bfloat16 or float32 `qkv` [b, s, (H + 2 Hk) d] with Hk = 2 key
    heads (the token's own value head and the previous token's), Hk
    dividing H, and d a multiple of 128 lanes."""
    if len(qkv_shape) != 3:
        return f"qkv of {len(qkv_shape)} dimensions"
    if jnp.dtype(qkv_dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return f"qkv of {jnp.dtype(qkv_dtype).name}"
    if kv_heads != 2:
        return f"{kv_heads} key heads, not 2"
    if heads % kv_heads or qkv_shape[-1] % (heads + 2 * kv_heads):
        return f"{heads} heads on {kv_heads} in {qkv_shape[-1]} lanes"
    d = qkv_shape[-1] // (heads + 2 * kv_heads)
    if d % 128:
        return f"a head of {d} is no multiple of 128 lanes"
    return None


def rows_a_block(s):
    """Rows a grid step holds: `ROWS`, or a shorter sequence's whole
    tiles."""
    return min(ROWS, -(-s // HALO) * HALO)


# ======================= a block's windows =======================

def _rows_of(n):
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)


def _up(x):
    """x[t + 1] down a window (its last row wraps)."""
    return pltpu.roll(x, x.shape[0] - 1, 0)


def _down(x):
    """x[t - 1] down a window (its first row wraps)."""
    return pltpu.roll(x, 1, 0)


def _lanes(h, d):
    return pl.ds(pl.multiple_of(h * d, 128), d)


def _unit_rows(w):
    """-> r [rows, 1] = rsqrt(|w|^2) a row, as `ops.cca_mix` bounds it."""
    return jax.lax.rsqrt(jnp.maximum(
        jnp.sum(w * w, axis=1, keepdims=True), 1e-24))


class _Head:
    """The two convolutions of one head over one window: `vec_ref`
    [4, n] float32 (a_0, a_1, b, b'), `gw_ref` [H + Hk, 2 d, d] in
    qkv's type."""

    def __init__(self, vec_ref, gw_ref, h, lanes, d):
        self.a0, self.a1, self.b, self.b2 = (
            vec_ref[j:j + 1, lanes] for j in range(4))
        self.A0, self.A1 = gw_ref[h, :d, :], gw_ref[h, d:, :]
        self.exact = jax.lax.Precision.HIGHEST \
            if gw_ref.dtype == F32 else None

    def dot(self, x, y, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(x, y, dims, precision=self.exact,
                                   preferred_element_type=F32)

    def z1(self, z, started):
        """The depthwise taps over a window of z (zero where the token
        is outside the row), in qkv's type; `started`: whether the rows
        before the block are the row's (else the row starts with the
        block, and their z1 is zero and not b)."""
        u = self.a0 * _down(z) + self.a1 * z + self.b
        return jnp.concatenate([jnp.where(started, u[:HALO], 0.0),
                                u[HALO:]], axis=0).astype(self.A0.dtype)

    def z2(self, z1):
        """The grouped taps: the window's rows from `HALO` on."""
        return _down(self.dot(z1, self.A0))[HALO:] \
            + self.dot(z1[HALO:], self.A1) + self.b2


# ======================= forward =======================

def _fwd_kernel(x_ref, before_ref, vec_ref, gw_ref, tau_ref, q_ref, k_ref,
                v_ref, *, d, H, Hk):
    G, n = H // Hk, (H + Hk) * d
    rows = x_ref.shape[0]
    dt = q_ref.dtype
    started = pl.program_id(1) > 0

    def window(lanes):
        """z from `HALO` rows before the block on. Rows beyond s are
        left as they come: they make rows beyond s, which nobody
        keeps."""
        return jnp.concatenate([
            jnp.where(started, before_ref[:, lanes].astype(F32), 0.0),
            x_ref[:, lanes].astype(F32)], axis=0)

    def group(g, carry):
        zk = window(_lanes(H + g, d))

        def query(j, sum_q):
            h = g * G + j
            lanes = _lanes(h, d)
            z = window(lanes)
            conv = _Head(vec_ref, gw_ref, h, lanes, d)
            w = conv.z2(conv.z1(z, started)) + 0.5 * (z + zk)[HALO:]
            q_ref[:, lanes] = (w * (_unit_rows(w) * math.sqrt(d))).astype(dt)
            return sum_q + z[HALO:]

        sum_q = jax.lax.fori_loop(0, G, query, jnp.zeros((rows, d), F32))
        conv = _Head(vec_ref, gw_ref, H + g, _lanes(H + g, d), d)
        w = conv.z2(conv.z1(zk, started)) \
            + 0.5 * (sum_q * (1.0 / G) + zk[HALO:])
        k_ref[:, _lanes(g, d)] = (
            w * _unit_rows(w) * tau_ref[:, _lanes(g, d)]).astype(dt)
        return carry

    jax.lax.fori_loop(0, Hk, group, 0)
    v_ref[:, :d] = x_ref[:, n:n + d]
    v_ref[:, d:] = _down(window(slice(n + d, n + 2 * d)))[HALO:].astype(dt)


# ======================= backward =======================

def _tile_sum(x):
    """[rows, d] -> [TILE, d]: the rows' sum, a tile apart."""
    return jnp.sum(x.reshape(x.shape[0] // TILE, TILE, -1), axis=0)


def _bwd_kernel(x_ref, before_ref, after_ref, vec_ref, gw_ref, tau_ref,
                dq_ref, dq_after, dk_ref, dk_after, dv_ref, dv_after,
                dx_ref, dgw_ref, sums_ref, *, s, d, H, Hk):
    G, n = H // Hk, (H + Hk) * d
    rows = x_ref.shape[0]
    dt = dx_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        dgw_ref[...] = jnp.zeros_like(dgw_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    started = pl.program_id(1) > 0
    t = pl.program_id(1) * rows - HALO + _rows_of(HALO + rows + HALO)
    seen = (t >= 0) & (t < s)
    live = seen[HALO:]

    def window(lanes):
        """z from `HALO` rows before the block to `HALO` after it, zero
        outside the row (a block over its end holds anything there)."""
        return jnp.where(seen, jnp.concatenate([
            before_ref[:, lanes], x_ref[:, lanes], after_ref[:, lanes]],
            axis=0).astype(F32), 0.0)

    def gradient(ref, after, lanes):
        """A gradient from the block's first row to `HALO` after it."""
        return jnp.where(live, jnp.concatenate([
            ref[:, lanes], after[:, lanes]], axis=0).astype(F32), 0.0)

    def head(h, z, mean, g, scale):
        """One head back through the norm and both convolutions -> (dw
        over the block, its part of dz by the convolutions, w r); adds
        to the parameters' sums."""
        lanes = _lanes(h, d)
        conv = _Head(vec_ref, gw_ref, h, lanes, d)
        z1 = conv.z1(z, started)
        w = conv.z2(z1) + mean
        r = _unit_rows(w)
        dw = (g - w * (r * r * jnp.sum(g * w, axis=1, keepdims=True))) \
            * (r * scale)
        dz2 = dw.astype(dt)
        du = conv.dot(dz2, conv.A1, _NT) + _up(conv.dot(dz2, conv.A0, _NT))
        dz = (conv.a1 * du + conv.a0 * _up(du))[:rows]
        du = du[:rows]
        dgw_ref[h] += conv.dot(
            z1[HALO:HALO + rows], jnp.concatenate(
                [_up(dw)[:rows], dw[:rows]], axis=1).astype(dt), _TN)
        for j, x in enumerate((du * _down(z)[HALO:HALO + rows],
                               du * z[HALO:HALO + rows], du, dw[:rows])):
            sums_ref[j, :, lanes] += _tile_sum(x)
        return dw[:rows], dz, (w * r)[:rows]

    def group(g, carry):
        k_lanes = _lanes(H + g, d)
        zk = window(k_lanes)

        def query_sum(j, sum_q):
            return sum_q + window(_lanes(g * G + j, d))[HALO:]

        sum_q = jax.lax.fori_loop(
            0, G, query_sum, jnp.zeros((rows + HALO, d), F32))
        gk = gradient(dk_ref, dk_after, _lanes(g, d))
        dwk, dzk, unit_k = head(
            H + g, zk, 0.5 * (sum_q * (1.0 / G) + zk[HALO:]), gk,
            tau_ref[:, _lanes(g, d)])
        sums_ref[4, :, k_lanes] += _tile_sum(
            gk[:rows] * unit_k * math.sqrt(d))

        def query(j, sum_dw):
            h = g * G + j
            lanes = _lanes(h, d)
            z = window(lanes)
            dw, dz, _wr = head(h, z, 0.5 * (z + zk)[HALO:],
                               gradient(dq_ref, dq_after, lanes),
                               math.sqrt(d))
            dx_ref[:, lanes] = (dz + 0.5 * dw + (0.5 / G) * dwk).astype(dt)
            return sum_dw + dw

        sum_dw = jax.lax.fori_loop(0, G, query, jnp.zeros((rows, d), F32))
        dx_ref[:, k_lanes] = (dzk + 0.5 * (dwk + sum_dw)).astype(dt)
        return carry

    jax.lax.fori_loop(0, Hk, group, 0)
    dx_ref[:, n:n + d] = dv_ref[:, :d]
    dx_ref[:, n + d:] = _up(gradient(dv_ref, dv_after, slice(d, 2 * d))
                            )[:rows].astype(dt)


# ======================= the calls =======================

def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _whole(x):
    return pl.BlockSpec(x.shape, lambda n, i: (0,) * x.ndim)


def _specs(s, rows):
    """-> the block specs of [b, s, lanes] arrays over a grid (batch
    row, row block): a block of rows, the `HALO` rows before it (any
    rows for the first block: they are before the row's start), the
    `HALO` rows after it (held inside the array: a block wholly beyond
    it would hold rows beyond s, which the kernel takes for zeros)."""
    per = rows // HALO
    most = pl.cdiv(s, HALO) - 1

    def block(lanes):
        return pl.BlockSpec((None, rows, lanes), lambda n, i: (n, i, 0))

    def before(lanes):
        return pl.BlockSpec((None, HALO, lanes), lambda n, i: (
            n, jnp.maximum(i * per - 1, 0), 0))

    def after(lanes):
        return pl.BlockSpec((None, HALO, lanes), lambda n, i: (
            n, jnp.minimum((i + 1) * per, most), 0))

    return block, before, after


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads",
                                             "interpret"))
@_pf.trace_timed_call("cca_mix_fwd")
def mix_fwd(qkv, vec, gw, tau, *, heads, kv_heads, interpret=False):
    """qkv [b, s, (H + 2 Hk) d]; vec [4, (H + Hk) d] float32 (a_0, a_1,
    b, b'); gw [H + Hk, 2 d, d] in qkv's type; tau [1, Hk d] float32
    (sqrt(d) tau_g on head g's lanes) -> q [b, s, H d], k, v
    [b, s, Hk d] in qkv's type. Under `jax.jit` and not inlined, as the
    backward's wrapper is: a step's layers and passes then trace and
    lower the kernel once."""
    b, s, width = qkv.shape
    H, Hk = heads, kv_heads
    d = width // (H + 2 * Hk)
    rows = rows_a_block(s)
    block, before, _after = _specs(s, rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, H=H, Hk=Hk),
        grid=(b, pl.cdiv(s, rows)),
        in_specs=[block(width), before(width), _whole(vec), _whole(gw),
                  _whole(tau)],
        out_specs=[block(H * d), block(Hk * d), block(Hk * d)],
        out_shape=[jax.ShapeDtypeStruct((b, s, H * d), qkv.dtype)]
        + [jax.ShapeDtypeStruct((b, s, Hk * d), qkv.dtype)] * 2,
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
        name="cca_mix_fwd",         # also the innermost jax.named_scope
    )(qkv, qkv, vec, gw, tau)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads",
                                             "interpret"))
@_pf.trace_timed_call("cca_mix_bwd")
def mix_bwd(qkv, vec, gw, tau, dq, dk, dv, *, heads, kv_heads,
            interpret=False):
    """The operands of `mix_fwd` and the gradients of what it made (as
    it made them: rows of H d and Hk d lanes in qkv's type) -> (the
    gradient of qkv in its type, of gw [H + Hk, d, 2 d] float32 (A_0's
    beside A_1's), the sums [5, (H + Hk) d] float32: of a_0, a_1, b,
    b', and of tau, to be added over a key head's lanes)."""
    b, s, width = qkv.shape
    H, Hk = heads, kv_heads
    d = width // (H + 2 * Hk)
    n = (H + Hk) * d
    rows = rows_a_block(s)
    block, before, after = _specs(s, rows)
    dx, dgw, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, s=s, d=d, H=H, Hk=Hk),
        grid=(b, pl.cdiv(s, rows)),
        in_specs=[block(width), before(width), after(width), _whole(vec),
                  _whole(gw), _whole(tau), block(H * d), after(H * d),
                  block(Hk * d), after(Hk * d), block(Hk * d),
                  after(Hk * d)],
        out_specs=[
            block(width),
            pl.BlockSpec((None, H + Hk, d, 2 * d),
                         lambda n, i: (n, 0, 0, 0)),
            pl.BlockSpec((None, 5, TILE, n), lambda n, i: (n, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
                   jax.ShapeDtypeStruct((b, H + Hk, d, 2 * d), F32),
                   jax.ShapeDtypeStruct((b, 5, TILE, n), F32)],
        # the sums stay in VMEM along the row axis
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
        name="cca_mix_bwd",
    )(qkv, qkv, qkv, vec, gw, tau, dq, dq, dk, dk, dv, dv)
    return dx, jnp.sum(dgw, axis=0), jnp.sum(sums, axis=(0, 2))


# ======================= the op =======================

def _operands(qkv, dw_weight, dw_bias, group_weight, group_bias,
              temperature, kv_heads):
    d = group_weight.shape[-1]
    vec = jnp.concatenate([dw_weight.astype(F32),
                           dw_bias.astype(F32)[None],
                           group_bias.astype(F32)[None]], axis=0)
    tau = jnp.repeat(temperature.astype(F32) * math.sqrt(d), d)
    return vec, group_weight.astype(qkv.dtype), tau.reshape(1, kv_heads * d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def mix(qkv, dw_weight, dw_bias, group_weight, group_bias, temperature,
        heads, kv_heads, interpret):
    """`ops.cca_mix` on the kernels -> q [b, s, H, d], k, v
    [b, s, Hk, d] in qkv's type; differentiable in qkv and the five
    parameters."""
    return _mix_fwd(qkv, dw_weight, dw_bias, group_weight, group_bias,
                    temperature, heads, kv_heads, interpret)[0]


def _mix_fwd(qkv, dw_weight, dw_bias, group_weight, group_bias, temperature,
             heads, kv_heads, interpret):
    params = (dw_weight, dw_bias, group_weight, group_bias, temperature)
    made = mix_fwd(qkv, *_operands(qkv, *params, kv_heads), heads=heads,
                   kv_heads=kv_heads, interpret=interpret)
    d = group_weight.shape[-1]
    return tuple(x.reshape(x.shape[:2] + (-1, d)) for x in made), (
        qkv, params)


def _mix_bwd(heads, kv_heads, interpret, res, grads):
    qkv, params = res
    dw_weight, dw_bias, group_weight, group_bias, temperature = params
    d = group_weight.shape[-1]
    dx, dgw, sums = mix_bwd(
        qkv, *_operands(qkv, *params, kv_heads),
        *(g.reshape(g.shape[:2] + (-1,)) for g in grads), heads=heads,
        kv_heads=kv_heads, interpret=interpret)
    dgw = jnp.concatenate([dgw[..., :d], dgw[..., d:]], axis=1)
    dtau = jnp.sum(sums[4, heads * d:].reshape(kv_heads, d), axis=1)
    return (dx, sums[:2].astype(dw_weight.dtype),
            sums[2].astype(dw_bias.dtype), dgw.astype(group_weight.dtype),
            sums[3].astype(group_bias.dtype), dtau.astype(temperature.dtype))


mix.defvjp(_mix_fwd, _mix_bwd)
