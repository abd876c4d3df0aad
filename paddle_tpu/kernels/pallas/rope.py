"""Pallas TPU kernel `rope_rotate`: rotate-half rotary position embedding
over the first `rot` dimensions of every head, one read of x and one
write.

    out[b, p, h, :] = x * cos[p] + rotate_half(x) * sin[p]   on [0, rot)
                      x                                      on [rot, d)
    rotate_half(x) = [-x2, x1]   (x1, x2 the halves of x[..., :rot])

x is taken as `[b, s, h * d]`, the view of `[b, s, h, d]` that the
projection writes and the flash kernels read: a head is a slab of d
lanes. A grid step holds a block of rows by a few heads (a DMA's
contiguous run is those heads' lanes of a row) and walks the rows in
chunks: a chunk of a head is widened to float32, turned and rounded once
to x's type.
rotate_half is a roll of the head's lanes: by rot / 2 it brings x1 under
the upper half, by d - rot / 2 it brings x2 under the lower, and each is
multiplied by the sine on the lanes it serves and by zero elsewhere (the
lower half's sine negated: rotate_half's sign). With rot == d the two
rolls are one.

The kernel reads cos and sin as float32 `[s, d]`: the model's `[s, rot]`
with ones and zeros on the d - rot lanes that pass through (`_tables`;
nothing to make where rot == d). Their block follows the rows only: the
grid's inner axes are the batch and the heads, so a block of them is
read once for all of those.

The op is linear in x, so its gradient is the same kernel run as the
transpose (`back`; `ops.rope_ops`): the gradient times the signed sine,
then rolled the other way, which is exact for any tables, and nothing of
x is kept for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from ...observability import perf as _pf

LANES = 128
CHUNK = 64          # rows turned at a time: whole tiles of either type
_HEADS = 8          # heads a block spans at most: runs of 2 KB of bf16
_BLOCK = 1 << 20    # bytes of x a grid step holds
F32 = jnp.float32


def reject_reason(x_shape, x_dtype, rot):
    """Why the kernel does not take this call (None: it does). It takes
    x [b, s, h, d] in bfloat16 or float32 with d a multiple of 128 lanes
    and an even rot <= d; any b, s and h."""
    d = x_shape[-1]
    if len(x_shape) != 4:
        return f"x of {len(x_shape)} dimensions"
    if jnp.dtype(x_dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return f"x of {jnp.dtype(x_dtype).name}"
    if d % LANES:
        return f"a head of {d} is no multiple of {LANES} lanes"
    if rot % 2 or not 0 < rot <= d:
        return f"rot {rot} of {d}"
    return None


def _tables(cos, sin, d):
    """cos, sin [s, rot] -> float32 [s, d]: ones and zeros on the d - rot
    lanes that pass through."""
    s, rot = cos.shape
    if rot == d:
        return cos.astype(F32), sin.astype(F32)
    return (jnp.concatenate([cos.astype(F32), jnp.ones((s, d - rot), F32)],
                            axis=1),
            jnp.concatenate([sin.astype(F32), jnp.zeros((s, d - rot), F32)],
                            axis=1))


def _kernel(cos_ref, sin_ref, x_ref, o_ref, *, d, rot, heads, back):
    """A block's rows in chunks, a chunk's heads one by one."""
    half = rot // 2

    def chunk(c, _):
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        cos, sin = cos_ref[rows, :], sin_ref[rows, :]
        lane = jax.lax.broadcasted_iota(jnp.int32, sin.shape, 1)
        # rotate_half's sign: -sin meets x2 brought down, sin meets x1
        # brought up
        signed = jnp.where(lane < half, -sin, sin)
        if rot < d:     # the lanes each roll serves; zero beyond rot
            lower = jnp.where(lane < half, signed, 0.0)
            upper = signed - lower
        for h in range(heads):
            x = x_ref[rows, h * d:(h + 1) * d].astype(F32)
            # back is the transpose: the sine first, then the other way
            if rot == d:        # where the two rolls are one
                y = x * cos + (pltpu.roll(x * signed, half, 1) if back
                               else pltpu.roll(x, half, 1) * signed)
            elif back:
                y = x * cos + pltpu.roll(x * lower, half, 1) \
                    + pltpu.roll(x * upper, d - half, 1)
            else:
                y = x * cos + pltpu.roll(x, d - half, 1) * lower \
                    + pltpu.roll(x, half, 1) * upper
            o_ref[rows, h * d:(h + 1) * d] = y.astype(o_ref.dtype)
        return _
    jax.lax.fori_loop(0, x_ref.shape[0] // CHUNK, chunk, 0)


def head_block(heads):
    """Heads of x a grid step holds: the most up to 8 that divide the
    head count."""
    return max(n for n in range(1, _HEADS + 1) if heads % n == 0)


def row_block(seq, row_bytes):
    """Rows a grid step holds, for its bytes a row: a block of 1 MB, in
    whole chunks and no more than cover the sequence."""
    rows = min(_BLOCK // row_bytes, seq + CHUNK - 1) // CHUNK * CHUNK
    return max(rows, CHUNK)


@functools.partial(jax.jit, static_argnames=("back", "interpret"),
                   inline=True)
@_pf.trace_timed_call("rope_rotate")
def rotate(x, cos, sin, *, back=False, interpret=False):
    """x [b, s, h, d]; cos, sin [s, rot]. Returns x turned (`back`: the
    transpose of that, which takes a gradient of the result to x's), in
    its type, and in its place where it has no other reader."""
    b, s, h, d = x.shape
    rot = cos.shape[-1]
    why = reject_reason(x.shape, x.dtype, rot)
    if why or cos.shape != (s, rot) or sin.shape != (s, rot):
        raise ValueError(f"rope_rotate: {why or 'tables'} (x {x.shape}, "
                         f"tables {cos.shape}, {sin.shape})")
    cos, sin = _tables(cos, sin, d)
    heads = head_block(h)
    rows = row_block(s, heads * d * x.dtype.itemsize)
    table = pl.BlockSpec((rows, d), lambda i, n, j: (i, 0))
    slab = pl.BlockSpec((None, rows, heads * d), lambda i, n, j: (n, i, j))
    out = pl.pallas_call(
        functools.partial(_kernel, d=d, rot=rot, heads=heads, back=back),
        grid=(pl.cdiv(s, rows), b, h // heads),
        in_specs=[table, table, slab],
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), x.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rope_rotate",     # also the innermost jax.named_scope
    )(cos, sin, x.reshape(b, s, h * d))
    return out.reshape(x.shape)
