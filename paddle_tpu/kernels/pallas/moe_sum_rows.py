"""Pallas TPU kernel `moe_sum_rows`: the way back from the experts' order
to the tokens' order of a dropless mixture-of-experts layer.

    out[t] = sum over j with held[t, j], in slot order, of
             (scale[t, j] *) float32(vals[rows[t, j]])           -> [T, d]

`rows[t, j]` is the row of `vals` that holds token t's j-th assignment.
Only rows that hold an assignment are moved, and they are moved as the
chip moves memory: the compiler takes no DMA of less than a tile of rows
from a tiled array in HBM (a single row of `vals` is 16 pieces in 16
tiles, each shared with 7 or 15 other rows), so the unit is a `WINDOW`
of 16 rows at a multiple of 16. What makes that cheap is the order
`ops.moe_ops.permutation` gives the rows: sorted by expert, one expert's
rows in token order. The rows that a tile of consecutive tokens has with
one expert are therefore consecutive, `tile_rows[i, g] ..
tile_rows[i + 1, g]`, and the windows that cover them hold few rows of
other tokens.

`plan` (plain XLA, once a permutation: both ways back share it) says
which windows a token tile needs, where each lands in the tile's VMEM
buffer, and for every token its held assignments first, as positions in
that buffer. Grid step i of `sum_rows` starts the DMAs of tile i's
windows into one of two buffers (a loop over the groups: a few 64 KB
copies each) and sums tile i - 1 out of the other: a scalar pass lists
the tile's held slots, and a loop over that list reads a slot's row from
the buffer at a dynamic sublane, widens it, scales it and adds it to its
token's row of a float32 tile, in slot order; one rounding at the end.
The work follows the held slots: a row of the buffer that no DMA wrote,
or that belongs to another tile's token, is never read.

On a TPU backend the kernel is the only path; elsewhere (CPU tests)
`ops.moe_ops._sum_slots` computes the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from ...observability import perf as _pf

WINDOW = 16         # rows a DMA moves: a whole tile of bf16 (or two of f32)
_VMEM_LIMIT = 100 * 1024 * 1024
_BUFFERS = 64 * 1024 * 1024     # what the two row buffers may take of it
F32 = jnp.float32


def _buffer_rows(tile, k, groups):
    """Rows a tile's windows take at worst: every assignment held, and a
    group with n rows here covered by at most (n + 15) // 16 + 1
    windows."""
    slots = tile * k
    rows = slots + (2 * WINDOW - 1) * min(groups, slots)
    return -(-rows // WINDOW) * WINDOW


def token_tile(tokens, k, groups, d, dtype):
    """Tokens a grid step sums: 512 (the longer a tile, the more of a
    window's rows are its own), halved while the two buffers do not fit
    or half would hold all the tokens, down to the 1024 slots a block of
    scalars must have."""
    row_bytes = d * jnp.dtype(dtype).itemsize
    least = max(8, 1024 // k)
    tile = max(512, least)
    while tile > least and (2 * _buffer_rows(tile, k, groups) * row_bytes
                            > _BUFFERS or tile >= 2 * tokens):
        tile //= 2
    return tile


def plan(rows, held, groups, tile_rows, tile):
    """What `sum_rows` reads, from a permutation: rows, held, groups
    [T, k] (the row of an assignment, whether its expert is held, the
    group it went to); tile_rows [tiles + 1, G] int32: the first row of
    group g that holds an assignment of a token >= i * tile (the rows of
    tile i with group g are tile_rows[i, g] .. tile_rows[i + 1, g]).
    Returns
      first, count [tiles, G]   a tile's windows of group g: `count` of
                                them from row `first` (a multiple of
                                WINDOW), laid one group after the other
      n_held [tiles]            held assignments of a tile's tokens
      before [tiles * tile, k]  those of the tile's earlier tokens (k
                                times: a block of scalars has 1024)
      at [tiles * tile, k]      a token's held assignments first, in slot
                                order: the row of the tile's buffer
      rank [T, k]               where among them an assignment is (k: not
                                held)."""
    T, k = rows.shape
    n, G = tile_rows.shape[0] - 1, tile_rows.shape[1]
    lo, hi = tile_rows[:-1], tile_rows[1:]
    first = lo // WINDOW * WINDOW
    count = jnp.where(hi > lo, (hi - first + WINDOW - 1) // WINDOW, 0)
    base = (jnp.cumsum(count, axis=1) - count) * WINDOW
    pad = ((0, n * tile - T), (0, 0))
    rows, groups = jnp.pad(rows, pad), jnp.pad(groups, pad)
    held = jnp.pad(held, pad)
    # a one-hot sum, not a gather: one term each, and XLA's gathers of
    # scalars are slow
    at = rows + jnp.sum(jnp.where(
        groups.reshape(n, tile, k, 1) == jnp.arange(G, dtype=jnp.int32),
        (base - first)[:, None, None, :], 0), axis=-1).reshape(-1, k)
    rank = jnp.where(held, jnp.cumsum(held, axis=1, dtype=jnp.int32) - 1, k)
    per_token = jnp.sum(held, axis=1, dtype=jnp.int32).reshape(n, tile)
    return dict(
        first=first, count=count, n_held=jnp.sum(per_token, axis=1),
        before=jnp.repeat((jnp.cumsum(per_token, axis=1) - per_token
                           ).reshape(-1, 1), k, axis=1),
        at=_held_first(at, rank), rank=rank[:T])


def _held_first(x, rank):
    """out[t, i] = x[t, j] where rank[t, j] == i (0 where none is)."""
    k = x.shape[1]
    return jnp.sum(jnp.where(
        rank[:, None, :] == jnp.arange(k, dtype=jnp.int32)[None, :, None],
        x[:, None, :], 0), axis=-1)


def _kernel(first_ref, count_ref, n_ref, at_ref, before_ref, scale_ref,
            vals_hbm, out_ref, buf, acc, sem, started, held, bits, *, k, tile,
            n_tiles, groups, scaled):
    i = pl.program_id(0)
    packed = buf.dtype.itemsize == 2
    # two bf16 rows share a 32-bit sublane: row 2q in the low half
    words = buf.bitcast(jnp.uint32) if packed else buf

    def window(slot, src, dst):
        return pltpu.make_async_copy(
            vals_hbm.at[pl.ds(pl.multiple_of(src, WINDOW), WINDOW)],
            buf.at[slot, pl.ds(pl.multiple_of(dst, WINDOW), WINDOW)],
            sem.at[slot])

    @pl.when(i < n_tiles)
    def _start():
        slot = i % 2

        def per_group(g, base):
            def start(w, _):
                window(slot, first_ref[i, g] + w * WINDOW,
                       base + w * WINDOW).start()
                return _
            jax.lax.fori_loop(0, count_ref[i, g], start, 0)
            return base + count_ref[i, g] * WINDOW
        started[slot] = jax.lax.fori_loop(0, groups, per_group,
                                          jnp.int32(0)) // WINDOW

    @pl.when(i > 0)
    def _sum():
        slot = (i - 1) % 2

        # the tile's held slots in order: a token's come first among its
        # k, so each token writes all k where its own begin and the next
        # token overwrites what was not held
        def note(t, _):
            for j in range(k):
                held[before_ref[t * k] + j] = t * k + j
            return _
        jax.lax.fori_loop(0, tile, note, 0)
        acc[...] = jnp.zeros(acc.shape, F32)

        def wait(_, c):
            window(slot, 0, 0).wait()
            return c
        jax.lax.fori_loop(0, started[slot], wait, 0)

        def add(e, _):
            s = held[e]
            t = s >> k.bit_length() - 1 if k & (k - 1) == 0 \
                else jax.lax.div(s, jnp.int32(k))
            at = at_ref[s]
            if packed:
                w = words[slot, pl.ds(at >> 1, 1), :]
                odd = (at & 1).astype(jnp.uint32)
                # through memory: a bitcast of the value would spread the
                # row's two vregs over sixteen
                bits[...] = (w >> (16 * odd)) << 16
                v = bits.bitcast(F32)[...]
            else:
                v = words[slot, pl.ds(at, 1), :].astype(F32)
            if scaled:
                v = v * scale_ref[s]
            # a token's slots follow each other: slot order
            acc[pl.ds(t, 1), :] += v
            return _
        jax.lax.fori_loop(0, n_ref[i - 1], add, 0)
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype",
                                             "interpret"), inline=True)
@_pf.trace_timed_call("moe_sum_rows")
def sum_rows(vals, plan, scale=None, *, tile, out_dtype, interpret=False):
    """vals [R, d] (R a multiple of WINDOW); plan: `plan`'s, for this
    `tile`; scale [T, k] float32 or None. Returns [T, d] in
    `out_dtype`."""
    R, d = vals.shape
    T, k = plan["rank"].shape
    n_tiles, G = plan["first"].shape
    if R % WINDOW or n_tiles != -(-T // tile):
        raise ValueError(f"sum_rows: {R} rows of values, {T} tokens in "
                         f"{n_tiles} tiles of {tile}")
    if vals.dtype not in (jnp.bfloat16, F32):   # the kernel widens by shifts
        raise ValueError(f"sum_rows: rows of {vals.dtype}")
    scaled = scale is not None
    if scaled:
        scale = _held_first(scale, plan["rank"])
        scale = jnp.pad(scale, ((0, n_tiles * tile - T), (0, 0)))
    else:
        scale = jnp.zeros((n_tiles * tile, k), F32)

    def summed(i, *_):
        return (jnp.maximum(i - 1, 0),)
    slots = pl.BlockSpec((tile * k,), summed, memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, tile=tile, n_tiles=n_tiles,
                          groups=G, scaled=scaled),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles + 1,),
            in_specs=[slots, slots, slots,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (tile, d), lambda i, *_: (jnp.maximum(i - 1, 0), 0)),
            scratch_shapes=[
                pltpu.VMEM((2, _buffer_rows(tile, k, G), d), vals.dtype),
                pltpu.VMEM((tile, d), F32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.SMEM((tile * k + k,), jnp.int32),
                pltpu.VMEM((1, d), jnp.uint32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_sum_rows",    # also the innermost jax.named_scope
    )(plan["first"], plan["count"], plan["n_held"], plan["at"].reshape(-1),
      plan["before"].reshape(-1), scale.reshape(-1), vals)
