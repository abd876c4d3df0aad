"""`kernels/pallas/grouped_matmul.py` against a loop over the groups:
the product and both gradients, with empty groups, groups that are no
multiple of the row tile and rows past the used prefix; on the XLA path
the CPU takes and with the Pallas kernels run by the interpreter."""
from importlib import import_module

import jax
import jax.numpy as jnp
import numpy as np
import pytest

gm = import_module("paddle_tpu.kernels.pallas.grouped_matmul")
T = gm.ROW_TILE

SIZES = {
    "mixed": [130, 0, 5, 128, 0, 300],
    "one_full": [0, 0, 0, 640, 0, 0],       # every row in one group
    "all_empty_but_last": [0, 0, 0, 0, 0, 1],
}


def _case(sizes, K=64, N=128, spare_tiles=3, seed=0):
    sizes = np.asarray(sizes, np.int32)
    G = len(sizes)
    R = gm.padded_rows(int(sizes.sum()), G) + spare_tiles * T
    starts, tile_group, used = gm.group_layout(jnp.asarray(sizes), R // T)
    starts = np.asarray(starts)
    rng = np.random.default_rng(seed)
    # rows outside every group are garbage on purpose: they may not leak
    x = rng.standard_normal((R, K)).astype(np.float32)
    live = np.zeros(R, bool)
    for s, n in zip(starts, sizes):
        live[s:s + n] = True
        x[s + n:s + -(-max(n, 1) // T) * T] = 0.0    # a group's padding
    w = rng.standard_normal((G, K, N)).astype(np.float32)
    return sizes, starts, live, jnp.asarray(x), jnp.asarray(w), int(used)


def _loop(x, w, sizes, starts):
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    for g, (s, n) in enumerate(zip(starts, sizes)):
        out = out.at[s:s + n].set(x[s:s + n] @ w[g])
    return out


def test_group_layout_pads_every_group_to_tiles_and_one_at_least():
    starts, tile_group, used = gm.group_layout(
        jnp.asarray(SIZES["mixed"], jnp.int32), 12)
    assert list(np.asarray(starts)) == [0, 256, 384, 512, 640, 768]
    assert int(used) == 9
    assert list(np.asarray(tile_group)) == [0, 0, 1, 2, 3, 4, 5, 5, 5,
                                            5, 5, 5]
    assert gm.padded_rows(131072, 64) == 131072 + 64 * T


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("name", list(SIZES))
def test_gmm_and_both_gradients_match_a_loop_over_groups(name, interpret):
    sizes, starts, live, x, w, _used = _case(SIZES[name])
    mask = jnp.asarray(live)[:, None]

    def ours(x, w):
        y = gm.gmm(x, w, jnp.asarray(sizes), interpret=interpret)
        return jnp.where(mask, y, 0)        # the caller's part: see gmm

    def loop(x, w):
        return _loop(x, w, sizes, starts)

    np.testing.assert_allclose(ours(x, w), loop(x, w), atol=2e-4)
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        (x.shape[0], w.shape[2])), jnp.float32)
    gx, gw = jax.grad(lambda x, w: jnp.sum(ours(x, w) * cot), (0, 1))(x, w)
    rx, rw = jax.grad(lambda x, w: jnp.sum(loop(x, w) * cot), (0, 1))(x, w)
    np.testing.assert_allclose(jnp.where(mask, gx, 0), rx, atol=2e-3)
    np.testing.assert_allclose(gw, rw, atol=2e-3)
    # an empty group's weights get a zero gradient, not a stale block
    for g, n in enumerate(sizes):
        if n == 0:
            assert not np.asarray(gw[g]).any()


def test_tiles_past_the_used_prefix_are_not_computed():
    """The interpreter leaves what a kernel never writes as it was
    handed out; rows past the prefix come back untouched by the
    product (here: not the product of their garbage)."""
    sizes, starts, live, x, w, used = _case(SIZES["mixed"], spare_tiles=4)
    y = gm.gmm(x, w, jnp.asarray(sizes), interpret=True)
    tail = np.asarray(y[used * T:])
    would_be = np.asarray(x[used * T:] @ w[-1])
    assert tail.shape[0] >= 4 * T
    assert not np.allclose(tail, would_be, atol=1e-3)


def test_rows_must_be_whole_tiles():
    with pytest.raises(ValueError, match="multiple"):
        gm.gmm(jnp.zeros((T + 1, 8)), jnp.zeros((2, 8, 8)),
               jnp.asarray([1, 1], jnp.int32))


@pytest.mark.parametrize("K,N,tiles", [
    (2048, 4096, (4, 4, 4)),    # zaya1-8b's gate_up: 16 MB a group
    (2048, 2048, (2, 2, 2)),    # ... and its down
    (2048, 1024, (1, 1, 1)),    # laguna-xs2's: a group's whole block,
    (512, 2048, (1, 1, 1)),     # the grid over the row tiles alone
    (2048, 2816, (3, 4, 3)),    # deepseek-v2-lite's gate_up, 22 x 128
    (1408, 2048, (2, 2, 2)),    # columns, and its down, 11 x 128 rows: no
])                              # divisor fits, the last tile is ragged
def test_wide_weight_blocks_are_cut_into_column_tiles(K, N, tiles):
    """bf16 at the three cells' widths, the kernels run by the
    interpreter: the product, its gradient to the rows (the weight's
    other axis cut) and `moe_gmm_dw` (its accumulator one column tile
    wide) against a loop over the groups on the same rounded operands."""
    steps = gm.pl.cdiv
    assert (steps(N, gm.column_tile(K, N, 2)),
            steps(K, gm.column_tile(N, K, 2)),
            steps(N, gm.column_tile(K, N, 2))) == tiles
    sizes, starts, live, x, w, _used = _case([130, 0, 5], K=K, N=N,
                                             spare_tiles=1)
    x, w = x.astype(jnp.bfloat16), (w / np.sqrt(K)).astype(jnp.bfloat16)
    mask = jnp.asarray(live)[:, None]

    def ours(x, w):
        y = gm.gmm(x, w, jnp.asarray(sizes), interpret=True)
        return jnp.where(mask, y, 0).astype(jnp.float32)

    def loop(x, w):
        return _loop(x.astype(jnp.float32), w.astype(jnp.float32), sizes,
                     starts)

    np.testing.assert_allclose(ours(x, w), loop(x, w), atol=3e-2)
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        (x.shape[0], N)), jnp.bfloat16).astype(jnp.float32)
    gx, gw = jax.grad(lambda x, w: jnp.sum(ours(x, w) * cot), (0, 1))(x, w)
    rx, rw = jax.grad(lambda x, w: jnp.sum(loop(x, w) * cot), (0, 1))(x, w)
    scale = float(jnp.abs(rx).max())
    np.testing.assert_allclose(
        jnp.where(mask, gx, 0).astype(jnp.float32) / scale,
        rx.astype(jnp.float32) / scale, atol=2e-2)
    scale = float(jnp.abs(rw).max())
    np.testing.assert_allclose(gw.astype(jnp.float32) / scale,
                               rw.astype(jnp.float32) / scale, atol=2e-2)
    assert not np.asarray(gw[1]).any()      # the empty group's block


def test_column_tile_keeps_a_block_within_four_mebibytes():
    assert gm.column_tile(2048, 4096, 2) == 1024
    assert gm.column_tile(4096, 2048, 2) == 512
    assert gm.column_tile(2048, 1024, 2) == 1024    # whole
    assert gm.column_tile(64, 128, 4) == 128
    assert gm.column_tile(4096, 1000, 4) == 1000    # no multiple of 128
    # the fewest steps within the budget, not the largest divisor
    assert gm.column_tile(2048, 2816, 2) == 1024    # 1024, 1024, 768
    assert gm.column_tile(2048, 1408, 2) == 768     # 768, 640
    assert gm.column_tile(1408, 2048, 2) == 1024
    assert gm.column_tile(2816, 2048, 2) == 512
    assert gm.column_tile(2048, 1408, 4) == 512     # 512, 512, 384
    assert gm.column_tile(512, 2048, 2) == 2048     # whole


@pytest.mark.parametrize("shape,note", [
    ((64, 2048, 1024), ""),         # laguna-xs2's and qwen3-next's: whole
    ((8, 2048, 4096), ", weight blocks in column tiles: gate_up 1024 x 4, "
     "down 1024 x 2, to the rows 512 x 4 and 1024 x 2"),    # zaya1-8b's
    ((8, 2048, 2816), ", weight blocks in column tiles: gate_up 1024 x 3 "
     "(the last 768), down 1024 x 2, to the rows 512 x 4 and 768 x 2 "
     "(the last 640)"),                                 # deepseek-v2-lite's
])
def test_tiles_note_names_the_four_products_tiles(shape, note):
    assert gm.tiles_note(shape) == note
