"""Pallas TPU kernels `gdn_operands_fwd` and `gdn_operands_bwd`: what a
Gated DeltaNet layer hands its delta rule, made from the projection's
output in one pass each way.

`qkvz` [b, s, Hk W] is `in_proj_qkvz`'s output as it stands, laid out by
key head: W = (2 + 2 rep) d lanes a head, q d | k d | its rep value
heads' v rep d | their z rep d. A key head's q | k | v (its first
L = (2 + rep) d lanes) go through the causal depthwise convolution of
`taps` taps (float32 sums), silu, and q and k are scaled to unit length
over d (q also by d^-0.5):

    pre[t] = sum_j w[j] * x[t - (taps - 1) + j]      x before t = 0 zero
    a = pre * sigmoid(pre)
    q = a_q * rsqrt(sum a_q^2 + 1e-6) * d^-0.5;  k likewise, times 1;  v = a_v

Out come q, k [b Hk, sp, d] and v [b Hv, sp, d], float32 and heads
first, sp = s up to whole chunks of the rule with zero rows beyond s:
`gated_delta.py`'s kernels read them as they lie (`[B, nc, CHUNK, d]` is
a view), q and k at the key heads.

A grid step holds `ROWS` rows of one key head and walks them in chunks
of `CHUNK` rows by all L lanes (one loop body, nothing unrolled by lane
group: the kernels' trace and lowering are set-up time of every run).
The rows a tap reaches before the block come through a
second block of `HALO` rows of the same array (zeros before t = 0); a
row shifted by j is a sublane roll with the neighbour's rows let in.

The backward makes `pre` again and goes back through the norms, the silu
and the convolution's transpose:

    da_q = scale r (dq - a_q r^2 <dq, a_q>),  r = rsqrt(sum a_q^2 + 1e-6)
    dpre = da * sigmoid(pre) * (1 + pre * (1 - sigmoid(pre)))
    dx[t] = sum_j w[taps - 1 - j] * dpre[t + j];  dw[j] = sum_t dpre[t] x[t - (taps - 1) + j]

dpre of the rows after the block is made from the block of x and of
the three gradients that follows it; the chunks run last to first, each
handing its
first rows' dpre to the one before. dx comes out by key head on the L
lanes the kernel read ([b, s, Hk L]); the z lanes between them are
zeros that XLA puts in where it adds z's own gradient, one pass that
writes the gradient of `qkvz` once. (Passing z's gradient through the
kernel instead saved that pass's read and cost the compiled step 0.39
GB of temporaries: PERF.md, PR 42.) dw comes as partial sums a row
block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability import perf as _pf
from .gated_delta import CHUNK as RULE_CHUNK

ROWS = 512          # rows of a key head a grid step holds
CHUNK = 64          # rows worked at a time, across the head's q | k | v
HALO = 16           # rows of a neighbour's block: a whole bfloat16 tile
TILE = 8            # rows of a float32 tile: what a shift lets in
EPS = 1e-6
F32 = jnp.float32
_VMEM_LIMIT = 64 * 1024 * 1024


def reject_reason(qkvz_shape, qkvz_dtype, taps, key_heads, value_heads):
    """Why the kernels do not take this call (None: they do). They take
    a bfloat16 or float32 `qkvz` [b, s, Hk (2 + 2 rep) d] with d a
    multiple of 128 lanes and at most 9 taps (a tap reaches no further
    back than a `TILE` of rows)."""
    if len(qkvz_shape) != 3:
        return f"qkvz of {len(qkvz_shape)} dimensions"
    if jnp.dtype(qkvz_dtype) not in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(F32)):
        return f"qkvz of {jnp.dtype(qkvz_dtype).name}"
    d = qkvz_shape[-1] // (2 * key_heads + 2 * value_heads)
    if d % 128:
        return f"a head of {d} is no multiple of 128 lanes"
    if not 1 <= taps <= TILE + 1:
        return f"{taps} taps"
    return None


def padded(s):
    """s up to whole chunks of the rule."""
    return -(-s // RULE_CHUNK) * RULE_CHUNK


def taps_by_head(w, key_heads, value_heads):
    """`conv_weight` [2 Hk d + Hv d, taps], its rows q | k | v each by
    head -> float32 [Hk, taps, (2 + rep) d]: a key head's q | k | v."""
    rep = value_heads // key_heads
    d = w.shape[0] // (2 * key_heads + value_heads)
    kw = key_heads * d
    by_head = jnp.concatenate([
        w[:kw].reshape(key_heads, d, -1),
        w[kw:2 * kw].reshape(key_heads, d, -1),
        w[2 * kw:].reshape(key_heads, rep * d, -1)], axis=1)
    return jnp.swapaxes(by_head, 1, 2).astype(F32)


# ======================= a chunk of rows =======================

def _rows_of(n):
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)


def _shift_down(x, before, j):
    """x[t - j] down a chunk; its first j rows are `before`'s last j
    (before: the `TILE` rows that precede the chunk)."""
    if j == 0:
        return x
    rolled = pltpu.roll(x, j, 0)
    head = jnp.where(_rows_of(TILE) < j, pltpu.roll(before, j, 0),
                     rolled[:TILE])
    if x.shape[0] == TILE:
        return head
    return jnp.concatenate([head, rolled[TILE:]], axis=0)


def _shift_up(x, after, j):
    """x[t + j] down a chunk; its last j rows are `after`'s first j
    (after: the `TILE` rows that follow the chunk)."""
    if j == 0:
        return x
    n = x.shape[0]
    rolled = pltpu.roll(x, n - j, 0)
    tail = jnp.where(_rows_of(TILE) >= TILE - j,
                     pltpu.roll(after, TILE - j, 0), rolled[n - TILE:])
    return jnp.concatenate([rolled[:n - TILE], tail], axis=0)


def _conv(x, before, w):
    """-> (pre, the taps' shifted copies of x). x [n, lanes] float32,
    w [taps, lanes]."""
    taps = w.shape[0]
    shifted = [_shift_down(x, before, taps - 1 - j) for j in range(taps)]
    pre = shifted[0] * w[0:1]
    for j in range(1, taps):
        pre = pre + shifted[j] * w[j:j + 1]
    return pre, shifted


def unit(a, scale):
    """scale * a * rsqrt(sum a^2 + 1e-6) over a head (the last axis)."""
    return a * (jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + EPS)
                * scale)


def _unit_bwd(a, g, scale):
    r = jax.lax.rsqrt(jnp.sum(a * a, axis=1, keepdims=True) + EPS)
    return (g - a * (r * r * jnp.sum(g * a, axis=1, keepdims=True))) \
        * (r * scale)


def _seen(ref, rows, lanes, first, s):
    """Rows of a block as float32, zero where the row (the block's
    `rows` start at row `first` of the sequence) is outside [0, s): a
    block over the array's edge holds anything there."""
    x = ref[rows, lanes].astype(F32)
    t = first + _rows_of(x.shape[0])
    return jnp.where((t >= 0) & (t < s), x, 0.0)


def _before(x_ref, before_ref, c, lanes, first, s):
    """The `TILE` rows before chunk c of the block that starts at row
    `first`: the block's own, or the halo block's last for its first
    chunk."""
    at = pl.multiple_of(jnp.maximum(c * CHUNK - HALO, 0), HALO)
    own = _seen(x_ref, pl.ds(at, HALO), lanes, first + at, s)
    halo = _seen(before_ref, slice(None), lanes, first - HALO, s)
    return jnp.where(c > 0, own, halo)[HALO - TILE:]


# ======================= the kernels =======================

def _fwd_kernel(x_ref, before_ref, w_ref, q_ref, k_ref, v_ref, *, s, d,
                rep):
    first = pl.program_id(2) * x_ref.shape[0]
    lanes = slice(0, (2 + rep) * d)     # q | k | v: z's lanes stay behind
    w = w_ref[...]

    def chunk(c, _):
        at = pl.multiple_of(c * CHUNK, CHUNK)
        rows = pl.ds(at, CHUNK)
        pre, _taps = _conv(_seen(x_ref, rows, lanes, first + at, s),
                           _before(x_ref, before_ref, c, lanes, first, s), w)
        # nothing beyond s: a zero row is a zero q, k and v
        a = jnp.where(first + at + _rows_of(CHUNK) < s,
                      pre * jax.nn.sigmoid(pre), 0.0)
        q_ref[rows, :] = unit(a[:, :d], d ** -0.5)
        k_ref[rows, :] = unit(a[:, d:2 * d], 1.0)
        for r in range(rep):
            v_ref[r, rows, :] = a[:, (2 + r) * d:(3 + r) * d]
        return _
    jax.lax.fori_loop(0, x_ref.shape[0] // CHUNK, chunk, 0)


def _dpre(x, before, w, d, dq, dk, dv, live):
    """-> (dpre of a run of rows, the taps' shifted x) from the gradients
    of what the rows became: dq, dk [n, d], dv a value head each."""
    pre, shifted = _conv(x, before, w)
    sig = jax.nn.sigmoid(pre)
    a = pre * sig
    da = jnp.concatenate([_unit_bwd(a[:, :d], dq, d ** -0.5),
                          _unit_bwd(a[:, d:2 * d], dk, 1.0), *dv], axis=1)
    return jnp.where(live, da * sig * (1.0 + pre * (1.0 - sig)), 0.0), shifted


def _bwd_kernel(x_ref, before_ref, after_ref, w_ref, dq_ref, dq_after,
                dk_ref, dk_after, dv_ref, dv_after, dx_ref, dw_ref, *, s, d,
                rep):
    rows_a_block = x_ref.shape[0]
    first = pl.program_id(2) * rows_a_block
    chunks = rows_a_block // CHUNK
    lanes = slice(0, (2 + rep) * d)
    w = w_ref[...]
    taps = w.shape[0]

    # dpre of the rows that follow the block
    end = first + rows_a_block
    after, _taps = _dpre(
        _seen(after_ref, slice(None), lanes, end, s)[:TILE],
        _seen(x_ref, pl.ds(rows_a_block - HALO, HALO), lanes, end - HALO,
              s)[HALO - TILE:],
        w, d, dq_after[...], dk_after[...],
        [dv_after[r] for r in range(rep)], end + _rows_of(TILE) < s)

    def chunk(i, carry):
        after, sums = carry
        c = chunks - 1 - i
        at = pl.multiple_of(c * CHUNK, CHUNK)
        rows = pl.ds(at, CHUNK)
        dpre, shifted = _dpre(
            _seen(x_ref, rows, lanes, first + at, s),
            _before(x_ref, before_ref, c, lanes, first, s), w, d,
            dq_ref[rows, :], dk_ref[rows, :],
            [dv_ref[r, rows, :] for r in range(rep)],
            first + at + _rows_of(CHUNK) < s)
        dx = dpre * w[taps - 1:taps]
        for j in range(1, taps):
            dx = dx + _shift_up(dpre, after, j) * w[taps - 1 - j:taps - j]
        dx_ref[rows, :] = dx.astype(dx_ref.dtype)
        # a tap's sum over the chunk's rows, a tile apart
        return dpre[:TILE], sums + jnp.concatenate(
            [jnp.sum((dpre * x).reshape(CHUNK // TILE, TILE, -1), axis=0)
             for x in shifted], axis=0)

    _after, sums = jax.lax.fori_loop(
        0, chunks, chunk, (after, jnp.zeros((taps * TILE, w.shape[1]), F32)))
    dw_ref[...] = jnp.sum(sums.reshape(taps, TILE, -1), axis=1)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _blocks(qkvz, key_heads, value_heads):
    """-> (the sizes, the block specs both kernels share) of a grid
    (batch row, key head, row block)."""
    b, s, width = qkvz.shape
    Hk, rep = key_heads, value_heads // key_heads
    d = width // (2 * key_heads + 2 * value_heads)
    W, L = (2 + 2 * rep) * d, (2 + rep) * d
    sp = padded(s)
    rows = min(ROWS, sp)

    def head(*block):       # of a [b Hk or b Hv, sp, d] array
        return pl.BlockSpec(block, lambda n, h, i: (n * Hk + h, i, 0))

    specs = dict(
        x=pl.BlockSpec((None, rows, W), lambda n, h, i: (n, i, h)),
        before=pl.BlockSpec((None, HALO, W), lambda n, h, i: (
            n, jnp.maximum(i * (rows // HALO) - 1, 0), h)),
        key=head(None, rows, d), values=head(rep, rows, d))
    return (b, s, rep, d, L, sp, rows, pl.cdiv(sp, rows)), specs


def _taps_spec(w):
    return pl.BlockSpec((None,) + w.shape[1:], lambda n, h, i: (h, 0, 0))


@functools.partial(jax.jit, static_argnames=(
    "key_heads", "value_heads", "interpret"))
@_pf.trace_timed_call("gdn_operands_fwd")
def operands_fwd(qkvz, w, *, key_heads, value_heads, interpret=False):
    """qkvz [b, s, Hk (2 + 2 rep) d]; w [Hk, taps, (2 + rep) d] float32
    (`taps_by_head`) -> q, k [b Hk, sp, d], v [b Hv, sp, d] float32.
    Under `jax.jit` and not inlined, as the backward's wrapper is: a
    step's layers and passes then trace and lower the kernel once."""
    (b, s, rep, d, _L, sp, _rows, blocks), sp_ = _blocks(
        qkvz, key_heads, value_heads)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, s=s, d=d, rep=rep),
        grid=(b, key_heads, blocks),
        in_specs=[sp_["x"], sp_["before"], _taps_spec(w)],
        out_specs=[sp_["key"], sp_["key"], sp_["values"]],
        out_shape=[jax.ShapeDtypeStruct((b * key_heads, sp, d), F32)] * 2
        + [jax.ShapeDtypeStruct((b * value_heads, sp, d), F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="gdn_operands_fwd",    # also the innermost jax.named_scope
    )(qkvz, qkvz, w)


@functools.partial(jax.jit, static_argnames=(
    "key_heads", "value_heads", "interpret"))
@_pf.trace_timed_call("gdn_operands_bwd")
def operands_bwd(qkvz, w, dq, dk, dv, *, key_heads, value_heads,
                 interpret=False):
    """The operands of `operands_fwd` and the gradients of what it made
    -> (the gradient of qkvz's q | k | v lanes [b, s, Hk (2 + rep) d] in
    its type, of w [Hk, taps, (2 + rep) d] float32)."""
    (b, s, rep, d, L, sp, rows, blocks), sp_ = _blocks(
        qkvz, key_heads, value_heads)
    Hk, W, taps = key_heads, qkvz.shape[-1] // key_heads, w.shape[1]

    def after(size, most):
        """The block of `size` rows that follows row block i, held inside
        the array: a block wholly beyond it would hold rows beyond s,
        which `_seen` and `live` zero."""
        return lambda i: jnp.minimum((i + 1) * (rows // size), most)

    x_after, g_after = after(HALO, pl.cdiv(s, HALO) - 1), after(
        TILE, sp // TILE - 1)
    key_after = pl.BlockSpec((None, TILE, d), lambda n, h, i: (
        n * Hk + h, g_after(i), 0))
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, s=s, d=d, rep=rep),
        grid=(b, Hk, blocks),
        in_specs=[
            sp_["x"], sp_["before"],
            pl.BlockSpec((None, HALO, W), lambda n, h, i: (
                n, x_after(i), h)),
            _taps_spec(w), sp_["key"], key_after, sp_["key"], key_after,
            sp_["values"],
            pl.BlockSpec((rep, TILE, d), lambda n, h, i: (
                n * Hk + h, g_after(i), 0))],
        out_specs=[
            pl.BlockSpec((None, rows, L), lambda n, h, i: (n, i, h)),
            pl.BlockSpec((None, None, None, taps, L),
                         lambda n, h, i: (n, i, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, s, Hk * L), qkvz.dtype),
                   jax.ShapeDtypeStruct((b, blocks, Hk, taps, L), F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="gdn_operands_bwd",
    )(qkvz, qkvz, qkvz, w, dq, dq, dk, dk, dv, dv)
    return dx, jnp.sum(dw, axis=(0, 1))


# ======================= the op =======================

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def operands(qkvz, w, key_heads, value_heads, interpret):
    """-> q, k [b, Hk, sp, d] and v [b, Hv, sp, d], float32;
    differentiable in qkvz and in w [Hk, taps, (2 + rep) d]
    (`taps_by_head`)."""
    return _operands_fwd(qkvz, w, key_heads, value_heads, interpret)[0]


def _operands_fwd(qkvz, w, key_heads, value_heads, interpret):
    made = operands_fwd(qkvz, w, key_heads=key_heads,
                        value_heads=value_heads, interpret=interpret)
    b = qkvz.shape[0]
    return tuple(x.reshape((b, -1) + x.shape[1:]) for x in made), (qkvz, w)


def _operands_bwd(key_heads, value_heads, interpret, res, grads):
    qkvz, w = res
    b, s, width = qkvz.shape
    dx, dw = operands_bwd(
        qkvz, w, *(x.astype(F32).reshape((-1,) + x.shape[2:])
                   for x in grads), key_heads=key_heads,
        value_heads=value_heads, interpret=interpret)
    # zeros on z's lanes, a key head at a time
    dx = jnp.pad(dx.reshape(b, s, key_heads, -1), (
        (0, 0), (0, 0), (0, 0), (0, (width - dx.shape[-1]) // key_heads)))
    return dx.reshape(qkvz.shape), dw


operands.defvjp(_operands_fwd, _operands_bwd)
