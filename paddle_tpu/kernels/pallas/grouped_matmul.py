"""Pallas TPU grouped matmul: the expert products of a dropless
mixture-of-experts layer.

    gmm(x [R, K], w [G, K, N], group_sizes [G]) -> [R, N]
        out[r] = x[r] @ w[g(r)]                      (kernel `moe_gmm`)
    its gradient to the rows is the same kernel on w's other axis, and
    gmm_dw(x [R, K], dy [R, N], group_sizes [G]) -> [G, K, N]
        dw[g] = sum over the rows r of group g of x[r]^T dy[r]
                                                     (kernel `moe_gmm_dw`)

The rows are sorted by group, and the group sizes are known only on the
device. Layout (`group_layout`): group g's rows start at a multiple of
`ROW_TILE`, every group owns at least one tile, and the rows of a
group's last tile past its size are padding the caller fills with rows
whose gradient is zero (`ops.moe_ops` does). So a row tile belongs to
one group, and the kernels are plain tiled matmuls whose weight block is
chosen by a prefetched table (tile -> group): consecutive tiles of one
group name the same weight block and the pipeline fetches it once, an
empty group's tile of padding leaves its dw block zero, and a group
boundary costs ROW_TILE / 2 rows of padding on average where a kernel
over unaligned groups would compute the straddling tile twice.

A weight block is a group's whole [K, N] where that is at most
`_BLOCK_BYTES` (4 MiB: [2048, 1024] in bf16, the largest the thin-expert
shapes have, two of which the pipeline holds beside the row tiles). A
wider expert's is cut into the fewest column tiles that budget allows
(`column_tile`: balanced multiples of 128, which need not divide the
columns), and the grid gains an outer axis over them: for one column
tile the kernel walks all the row tiles, so a group's consecutive tiles
still name one weight block and it is fetched once, and the rows are
read once a column tile: every column step is one more pass over the
rows, which is why the steps are the fewest and not the tile the
largest divisor (1408 = 11 x 128 columns had eleven tiles of 128 that
way). `moe_gmm_dw`'s float32 accumulator is one column tile wide the
same way. Where the tile does not divide the columns the last block is
ragged: it hangs over the array's edge. Every product here has
independent columns (`out[r, n]`, `dx[r, n]` and `dw[g, k, n]` each
depend on column n of one operand alone), so whatever the edge block
reads past the array lands only in output columns that the edge
block's write drops.

Shapes are static and sized by the caller for its worst case; the
tiles past the used prefix are not visited: their grid steps name the
last used tile's blocks (nothing is fetched) and do no work, and their
rows of the output are left as they were (unspecified).

bf16 (or float32) operands, float32 accumulation. On a TPU backend the
kernels are the only path; elsewhere (CPU tests) `jax.lax.ragged_dot`
over the padded sizes computes the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from ...observability import perf as _pf

from .flash_attention import _pallas_available

ROW_TILE = 128
_VMEM_LIMIT = 100 * 1024 * 1024
_BLOCK_BYTES = 4 * 1024 * 1024
F32 = jnp.float32


def column_tile(contract, cols, itemsize):
    """Columns of a weight block [contract, columns]: all `cols` where
    that is at most `_BLOCK_BYTES`, else a multiple of 128 (128 at
    least): the fewest tiles whose block stays within it, balanced, so
    that only the last tile is short. A divisor of `cols` that gives as
    few tiles is what comes out ([2048, 4096] in bf16: four of 1024);
    where none does ([2048, 2816]: its best divisor, 256, makes eleven)
    the tiles are 1024, 1024 and a ragged 768."""
    if contract * cols * itemsize <= _BLOCK_BYTES or cols % 128:
        return cols
    widest = max(_BLOCK_BYTES // (contract * itemsize * 128), 1) * 128
    steps = pl.cdiv(cols, widest)
    return pl.cdiv(cols, steps * 128) * 128


def group_layout(group_sizes, n_tiles, row_tile=ROW_TILE):
    """(row_starts [G], tile_group [n_tiles], tiles_used) of the padded
    layout: group g's rows are row_starts[g] .. + group_sizes[g], tile t
    < tiles_used belongs to group tile_group[t]; later tiles repeat the
    last used one's group."""
    sizes = group_sizes.astype(jnp.int32)
    tiles = jnp.maximum((sizes + row_tile - 1) // row_tile, 1)
    ends = jnp.cumsum(tiles)
    used = ends[-1]
    t = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), used - 1)
    tile_group = jnp.searchsorted(ends, t, side="right").astype(jnp.int32)
    return (ends - tiles) * row_tile, tile_group, used


def padded_rows(assignments, groups, row_tile=ROW_TILE):
    """Rows the layout needs at worst for `assignments` rows in
    `groups` groups."""
    return (assignments // row_tile + groups) * row_tile


# ======================= kernels =======================

def _gmm_kernel(group_ref, used_ref, x_ref, w_ref, o_ref, *, transpose_w,
                row_axis):
    del group_ref

    @pl.when(pl.program_id(row_axis) < used_ref[0])
    def _():
        contract = (((1,), (1 if transpose_w else 0,)), ((), ()))
        o_ref[:] = jax.lax.dot_general(
            x_ref[:], w_ref[0], contract,
            preferred_element_type=F32).astype(o_ref.dtype)


def _dw_kernel(group_ref, used_ref, x_ref, dy_ref, dw_ref, acc_ref, *,
               row_axis):
    i = pl.program_id(row_axis)
    last = used_ref[0] - 1
    here = group_ref[jnp.minimum(i, last)]

    @pl.when(i <= last)
    def _():
        @pl.when(jnp.logical_or(i == 0,
                                group_ref[jnp.maximum(i - 1, 0)] != here))
        def _first():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += jax.lax.dot_general(
            x_ref[:], dy_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=F32)

        @pl.when(jnp.logical_or(i == last, group_ref[i + 1] != here))
        def _last():
            dw_ref[0] = acc_ref[:].astype(dw_ref.dtype)


def _tile(i, used_ref):
    return jnp.minimum(i, used_ref[0] - 1)


def _grid(n_tiles, n_cols):
    """(grid, semantics, ij): the row tiles alone where a weight block
    is a group's whole (the program the thin-expert shapes have always
    had), else the column tiles outside them; `ij(index map arguments)`
    -> (column tile, row tile, group table, used)."""
    if n_cols == 1:
        return ((n_tiles,), ("arbitrary",),
                lambda i, g, u: (0, i, g, u))
    return ((n_cols, n_tiles), ("arbitrary", "arbitrary"),
            lambda j, i, g, u: (j, i, g, u))


@functools.partial(jax.jit, static_argnames=("transpose_w", "interpret"),
                   inline=True)
@_pf.trace_timed_call("moe_gmm")
def _gmm_call(x, w, tile_group, used, *, transpose_w, interpret):
    R, K = x.shape
    N = w.shape[1] if transpose_w else w.shape[2]
    cols = column_tile(K, N, x.dtype.itemsize)
    grid, semantics, ij = _grid(R // ROW_TILE, pl.cdiv(N, cols))

    def rows(*a):
        _j, i, _g, u = ij(*a)
        return _tile(i, u), 0

    def weight(*a):
        j, i, g, _u = ij(*a)
        return (g[i], j, 0) if transpose_w else (g[i], 0, j)

    def out(*a):
        j, i, _g, u = ij(*a)
        return _tile(i, u), j

    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w,
                          row_axis=len(grid) - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((ROW_TILE, K), rows),
                pl.BlockSpec((1, cols, K) if transpose_w else (1, K, cols),
                             weight),
            ],
            out_specs=pl.BlockSpec((ROW_TILE, cols), out),
        ),
        out_shape=jax.ShapeDtypeStruct((R, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm",     # also the innermost jax.named_scope
    )(tile_group, used, x, w)


@functools.partial(jax.jit, static_argnames=("groups", "interpret"),
                   inline=True)
@_pf.trace_timed_call("moe_gmm_dw")
def _dw_call(x, dy, tile_group, used, *, groups, interpret):
    R, K = x.shape
    N = dy.shape[1]
    cols = column_tile(K, N, x.dtype.itemsize)
    grid, semantics, ij = _grid(R // ROW_TILE, pl.cdiv(N, cols))
    # one entry past the table's end for the kernel's look at tile i + 1
    table = jnp.concatenate([tile_group, tile_group[-1:]])

    def rows(*a):
        _j, i, _g, u = ij(*a)
        return _tile(i, u), 0

    def grads(*a):
        j, i, _g, u = ij(*a)
        return _tile(i, u), j

    def out(*a):
        j, i, g, _u = ij(*a)
        return g[i], 0, j

    return pl.pallas_call(
        functools.partial(_dw_kernel, row_axis=len(grid) - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((ROW_TILE, K), rows),
                pl.BlockSpec((ROW_TILE, cols), grads),
            ],
            out_specs=pl.BlockSpec((1, K, cols), out),
            scratch_shapes=[pltpu.VMEM((K, cols), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, K, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_dw",
    )(table, used, x, dy)


# ======================= the XLA path =======================

def _padded_sizes(tile_group, used, groups):
    """Rows of every group's tiles, padding included."""
    n = tile_group.shape[0]
    live = jnp.arange(n) < used
    return jnp.zeros((groups,), jnp.int32).at[tile_group].add(
        jnp.where(live, ROW_TILE, 0))


def _xla_gmm(x, w, tile_group, used, transpose_w):
    if transpose_w:
        w = jnp.swapaxes(w, 1, 2)
    return jax.lax.ragged_dot(
        x, w, _padded_sizes(tile_group, used, w.shape[0]),
        preferred_element_type=F32).astype(x.dtype)


def _xla_dw(x, dy, tile_group, used, groups):
    live = jnp.arange(tile_group.shape[0]) < used
    onehot = (jnp.logical_and(
        tile_group[:, None] == jnp.arange(groups)[None], live[:, None])
    ).astype(F32)                                       # [tiles, G]
    xt = x.reshape(-1, ROW_TILE, x.shape[1]).astype(F32)
    dyt = dy.reshape(-1, ROW_TILE, dy.shape[1]).astype(F32)
    per_tile = jnp.einsum("trk,trn->tkn", jnp.where(
        live[:, None, None], xt, 0), jnp.where(live[:, None, None], dyt, 0))
    return jnp.einsum("tg,tkn->gkn", onehot, per_tile).astype(x.dtype)


# ======================= the entry =======================

def _product(x, w, tile_group, used, use_pallas, interpret, transpose_w):
    if use_pallas:
        return _gmm_call(x, w, tile_group, used, transpose_w=transpose_w,
                         interpret=interpret)
    return _xla_gmm(x, w, tile_group, used, transpose_w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmm(x, w, tile_group, used, use_pallas, interpret):
    return _product(x, w, tile_group, used, use_pallas, interpret, False)


def _gmm_fwd(x, w, tile_group, used, use_pallas, interpret):
    return (_product(x, w, tile_group, used, use_pallas, interpret, False),
            (x, w, tile_group, used))


def _gmm_bwd(use_pallas, interpret, res, dy):
    x, w, tile_group, used = res
    dy = dy.astype(x.dtype)
    dx = _product(dy, w, tile_group, used, use_pallas, interpret, True)
    if use_pallas:
        dw = _dw_call(x, dy, tile_group, used, groups=w.shape[0],
                      interpret=interpret)
    else:
        dw = _xla_dw(x, dy, tile_group, used, w.shape[0])
    return dx, dw.astype(w.dtype), None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm_path() -> str:
    return "pallas" if _pallas_available() else "xla"


def gmm(x, w, group_sizes, interpret=False):
    """x [R, K] (R a multiple of ROW_TILE, rows in `group_layout`'s
    order), w [G, K, N], group_sizes [G] int32 -> [R, N] in x's type.
    Rows of tiles past the used prefix are unspecified on the way out
    and must carry no gradient on the way back; dw of such rows and of
    padding is what the caller's padding rows make it (zero, if their
    gradient is zero)."""
    R = x.shape[0]
    if R % ROW_TILE:
        raise ValueError(f"gmm: {R} rows are no multiple of {ROW_TILE}")
    _starts, tile_group, used = group_layout(group_sizes, R // ROW_TILE)
    return _gmm(x, w.astype(x.dtype), tile_group, used.reshape(1),
                bool(interpret or _pallas_available()), bool(interpret))


def tiles_note(gate_up_shape, itemsize=2):
    """For the `moe` note: how the weight blocks of the two products and
    of each one's gradient to the rows are cut into column tiles
    ("tile x steps", `moe_gmm_dw`'s are its forward product's), or
    nothing where each is a group's whole."""
    _g, hidden, two_wide = gate_up_shape
    wide = two_wide // 2
    products = ((hidden, two_wide), (wide, hidden),
                (two_wide, hidden), (hidden, wide))
    tiles = [column_tile(k, n, itemsize) for k, n in products]
    if tiles == [n for _k, n in products]:
        return ""
    gate_up, down, gate_up_rows, down_rows = (
        f"{t} x {pl.cdiv(n, t)}" + (f" (the last {n % t})" if n % t else "")
        for t, (_k, n) in zip(tiles, products))
    return (f", weight blocks in column tiles: gate_up {gate_up}, down "
            f"{down}, to the rows {gate_up_rows} and {down_rows}")
