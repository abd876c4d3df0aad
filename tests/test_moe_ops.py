"""The dropless expert layer on the CPU: `ops.moe_route`, the
permutation, `ops.moe_experts` and `nn.SparseExpertFFN` against the
routed sum written out expert by expert; no token is dropped whatever
the imbalance; the shares of an expert-parallel deployment add up to the
uncut layer; the rotary tables against the formula by hand."""
import math
from importlib import import_module

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, ops

mo = import_module("paddle_tpu.ops.moe_ops")
gm = import_module("paddle_tpu.kernels.pallas.grouped_matmul")
sr = import_module("paddle_tpu.kernels.pallas.moe_sum_rows")


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _dense_sum(x, weights, experts, w_gu, w_down, first):
    """sum over a token's choices among the held experts, a loop."""
    width = w_down.shape[1]
    y = jnp.zeros_like(x)
    for g in range(w_gu.shape[0]):
        w = jnp.sum(jnp.where(experts == first + g, weights, 0.0), axis=-1)
        a = x @ w_gu[g]
        y = y + w[:, None] * ((_silu(a[:, :width]) * a[:, width:])
                              @ w_down[g])
    return y


def _weights(seed, count, d, width):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((count, d, 2 * width)) * 0.2,
                        jnp.float32),
            jnp.asarray(rng.standard_normal((count, width, d)) * 0.2,
                        jnp.float32))


def test_moe_route_scores_topk_and_normalised_weights():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    wr = jnp.asarray(rng.standard_normal((16, 12)), jnp.float32)
    w, e = ops.moe_route(pt.to_tensor(x), pt.to_tensor(wr), 3, 2.5)
    w, e = w.numpy(), e.numpy()
    s = np.asarray(jax.nn.sigmoid(x @ wr))
    want = np.argsort(-s, axis=1)[:, :3]
    assert (np.sort(e, 1) == np.sort(want, 1)).all()
    np.testing.assert_allclose(w.sum(1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(
        w, 2.5 * np.take_along_axis(s, e, 1)
        / np.take_along_axis(s, e, 1).sum(1, keepdims=True), rtol=1e-5)
    assert w.dtype == np.float32 and e.dtype == np.int32


def test_moe_route_is_float32_under_amp():
    from paddle_tpu import amp
    x = pt.to_tensor(np.ones((4, 8), np.float32)).astype("bfloat16")
    wr = pt.to_tensor(np.ones((8, 6), np.float32))
    with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
        w, _e = ops.moe_route(x, wr, 2)
    assert w.numpy().dtype == np.float32


def test_permutation_puts_every_held_assignment_in_its_experts_rows():
    rng = np.random.default_rng(1)
    experts = jnp.asarray(rng.integers(0, 16, (40, 4)), jnp.int32)
    p = mo.permutation(experts, 4, 8)
    flat = np.asarray(experts).reshape(-1)
    held = (flat >= 4) & (flat < 12)
    counts = np.bincount(flat[held] - 4, minlength=8)
    assert (np.asarray(p["counts"]) == counts).all()
    starts, _tg, used = gm.group_layout(p["counts"],
                                        p["live_row"].shape[0] // gm.ROW_TILE)
    assert int(p["rows_used"]) == int(used) * gm.ROW_TILE
    live, slot = np.asarray(p["live_row"]), np.asarray(p["slot_of_row"])
    assert live.sum() == held.sum()
    assert sorted(slot[live]) == sorted(np.nonzero(held)[0])   # each once
    for g, (s, n) in enumerate(zip(np.asarray(starts), counts)):
        assert live[s:s + n].all() and (flat[slot[s:s + n]] == 4 + g).all()
        assert (np.diff(slot[s:s + n]) > 0).all()       # token order
    back = np.asarray(p["row_of_slot"])
    assert (slot[back[held]] == np.nonzero(held)[0]).all()
    assert (np.asarray(p["held_slot"]) == held).all()


def _choices(rng, T, k, E):
    return np.stack([rng.permutation(E)[:k] for _ in range(T)])


def _none_and_all_eight(rng, T, k, E):
    """Token 0 chooses the held experts 4..11 and token 1 none of them."""
    experts = _choices(rng, T, k, E)
    experts[0] = 4 + rng.permutation(8)
    experts[1] = np.concatenate([rng.permutation(4),
                                 12 + rng.permutation(E - 12)[:k - 4]])
    return experts


def _one_takes_every_token(rng, T, k, E):
    others = np.stack([1 + rng.permutation(E - 1)[:k - 1] for _ in range(T)])
    return np.concatenate([np.zeros((T, 1), np.int64), others], axis=1)


# name: tokens, top_k, experts, held (first, count), token tile, rows' type,
# with the routing weights, the choices, rows that hold nothing are NaN
WAY_BACK = {
    "a_quarter_held": (64, 8, 32, (0, 8), 16, "bfloat16", True, _choices,
                       False),
    "every_expert_held": (48, 4, 8, (0, 8), 16, "float32", True, _choices,
                          False),
    "one_expert_of_many": (64, 8, 64, (5, 1), 16, "bfloat16", False,
                           _choices, False),
    "a_token_with_none_and_one_with_all_eight": (
        40, 8, 32, (4, 8), 8, "float32", True, _none_and_all_eight, False),
    "one_expert_takes_every_token": (64, 4, 16, (0, 4), 16, "bfloat16", True,
                                     _one_takes_every_token, False),
    "tokens_no_multiple_of_the_tile": (37, 4, 16, (4, 8), 16, "float32",
                                       True, _choices, False),
    "without_weights_float32": (48, 8, 32, (8, 16), 16, "float32", False,
                                _choices, False),
    "what_was_never_fetched_is_poison": (64, 8, 32, (8, 8), 16, "bfloat16",
                                         True, _choices, True),
}


@pytest.mark.parametrize("name", sorted(WAY_BACK))
def test_the_way_back_kernel_sums_the_held_rows_as_sum_slots_does(name):
    """`moe_sum_rows` under the interpreter (whose VMEM starts as NaN: a
    row of the buffer that no DMA wrote would show) against the gather
    over all slots and against the sum written out slot by slot."""
    T, k, E, (first, count), tile, dtype, scaled, choose, poison = \
        WAY_BACK[name]
    rng = np.random.default_rng(sorted(WAY_BACK).index(name))
    experts = jnp.asarray(choose(rng, T, k, E), jnp.int32)
    p = mo.permutation(experts, first, count, tile)
    live = np.asarray(p["live_row"])
    vals = rng.standard_normal((live.shape[0], 128)).astype(np.float32)
    vals = np.asarray(jnp.asarray(vals, dtype).astype(jnp.float32))
    scale = rng.uniform(0.1, 1.0, (T, k)).astype(np.float32)
    held = np.asarray(p["held_slot"]).reshape(T, k)
    rows = np.asarray(p["row_of_slot"]).reshape(T, k)
    want = np.zeros((T, 128), np.float32)
    for j in range(k):      # slot order, float32
        term = vals[rows[:, j]] * (scale[:, j, None] if scaled else 1)
        want += np.where(held[:, j, None], term.astype(np.float32), 0)
    if poison:
        vals = np.where(live[:, None], vals, np.nan)
    args = (jnp.asarray(vals, dtype), p, k)
    w = jnp.asarray(scale) if scaled else None
    got = mo._way_back(*args, (tile, True), w)
    gather = mo._way_back(*args, None, w)
    assert got.dtype == gather.dtype == jnp.dtype(dtype)
    got, gather = (np.asarray(a, np.float32) for a in (got, gather))
    assert np.isfinite(got).all()
    tol = dict(atol=1e-6, rtol=1e-6) if dtype == "float32" \
        else dict(atol=0, rtol=2 ** -8)     # one rounding to bf16
    np.testing.assert_allclose(got, want, **tol)
    np.testing.assert_allclose(got, gather, **tol)
    fetched = int(np.asarray(p["way_back"]["count"]).sum()) * sr.WINDOW
    assert held.sum() <= fetched <= held.sum() + 2 * sr.WINDOW * min(
        count * -(-T // tile), max(int(held.sum()), 1))


def test_the_token_tile_fits_its_buffers_and_a_block_of_scalars():
    """512 tokens at the cell's shapes; fewer where 256 experts' windows
    would not fit; never under the 1024 slots a block of scalars has."""
    assert sr.token_tile(16384, 8, 64, 2048, jnp.bfloat16) == 512
    assert sr.token_tile(16384, 8, 64, 2048, jnp.float32) == 256
    assert sr.token_tile(16384, 8, 256, 2048, jnp.bfloat16) == 128
    assert sr.token_tile(48, 4, 8, 32, jnp.float32) == 256
    assert sr.token_tile(48, 1, 8, 32, jnp.float32) == 1024


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("first,count", [(0, 16), (4, 8), (12, 4)])
def test_moe_experts_is_the_routed_sum_and_so_are_its_gradients(
        first, count, interpret):
    """`interpret`: the grouped-matmul kernels and the way back's kernel
    (`combine_rows` forward, `take_rows` backward) under the
    interpreter."""
    rng = np.random.default_rng(2)
    T, d, width, k = 48, 32, 16, 4
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    wr = jnp.asarray(rng.standard_normal((d, 16)), jnp.float32)
    w_gu, w_down = _weights(3, count, d, width)
    cot = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)

    def ours(x, wr, w_gu, w_down):
        w, e = mo.moe_route.op_def.fn(x, wr, k, 2.5)
        y, counts = mo.moe_experts.op_def.fn(x, w, e, w_gu, w_down, first,
                                             interpret)
        return jnp.sum(y * cot), (y, counts, e)

    def loop(x, wr, w_gu, w_down):
        w, e = mo.moe_route.op_def.fn(x, wr, k, 2.5)
        y = _dense_sum(x, w, e, w_gu, w_down, first)
        return jnp.sum(y * cot), y

    (_, (y, counts, e)), got = jax.value_and_grad(
        ours, (0, 1, 2, 3), has_aux=True)(x, wr, w_gu, w_down)
    (_, want_y), want = jax.value_and_grad(
        loop, (0, 1, 2, 3), has_aux=True)(x, wr, w_gu, w_down)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=1e-4)
    flat = np.asarray(e).reshape(-1) - first
    assert (np.asarray(counts) == np.bincount(
        flat[(flat >= 0) & (flat < count)], minlength=count)).all()


@pytest.mark.parametrize("case", ["all_on_one", "one_gets_none"])
def test_no_token_is_dropped_whatever_the_imbalance(case):
    """Every token chooses expert 5 (and three more): the worst case of
    one group; or no token chooses expert 6: an empty group."""
    rng = np.random.default_rng(4)
    T, d, width, k = 64, 32, 16, 4
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    w_gu, w_down = _weights(5, 8, d, width)
    if case == "all_on_one":
        others = np.stack([rng.permutation([0, 1, 2, 3, 4, 6, 7])[:k - 1]
                           for _ in range(T)])
        experts = np.concatenate([np.full((T, 1), 5), others], axis=1)
    else:
        experts = np.stack([rng.permutation([0, 1, 2, 3, 4, 5, 7])[:k]
                            for _ in range(T)])
    experts = jnp.asarray(experts, jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (T, k)), jnp.float32)
    y, counts = mo.moe_experts.op_def.fn(x, weights, experts, w_gu, w_down,
                                         0)
    np.testing.assert_allclose(
        y, _dense_sum(x, weights, experts, w_gu, w_down, 0), atol=2e-5)
    assert int(np.asarray(counts).sum()) == T * k       # all of them
    if case == "all_on_one":
        assert int(counts[5]) == T
    else:
        assert int(counts[6]) == 0


def test_four_shares_of_four_experts_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: 16 experts, four chips with
    four each. Every share routes over all 16 and computes its own
    experts' part; the parts, with the shared expert counted once, are
    the uncut layer's output."""
    pt.seed(0)
    d, width = 32, 16
    whole = nn.SparseExpertFFN(d, width, num_experts=16, top_k=4,
                               shared_width=16, std=0.3)
    whole.eval()
    x = pt.to_tensor(np.random.default_rng(6).standard_normal(
        (2, 24, d)).astype(np.float32))
    want, counts = whole(x)
    shared = whole.shared_expert(x).numpy()
    total = np.zeros_like(want.numpy())
    seen = []
    for first in (0, 4, 8, 12):
        part = nn.SparseExpertFFN(d, width, num_experts=16, top_k=4,
                                  held=(first, 4), shared_width=16)
        part.router.weight._data = whole.router.weight._data
        part.gate_up_proj._data = whole.gate_up_proj._data[first:first + 4]
        part.down_proj._data = whole.down_proj._data[first:first + 4]
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(part.shared_expert, name).weight._data = getattr(
                whole.shared_expert, name).weight._data
        part.eval()
        y, c = part(x)
        total += y.numpy() - shared         # the routed part alone
        seen.append(c.numpy())
    np.testing.assert_allclose(total + shared, want.numpy(), atol=3e-5)
    assert (np.concatenate(seen) == counts.numpy()).all()
    assert int(counts.numpy().sum()) == 2 * 24 * 4


def test_held_must_be_a_range_of_the_experts():
    with pytest.raises(ValueError, match="range"):
        nn.SparseExpertFFN(8, 4, num_experts=16, held=(14, 4))


def test_yarn_frequencies_by_hand():
    """Laguna-XS.2's full-attention rule: 64 rotated dimensions, theta
    500000, factor 64, original length 4096, beta_fast 64, beta_slow 1.
    c(n) = 64 ln(4096 / (2 pi n)) / (2 ln 500000): c(64) = 5.66 so low
    5, c(1) = 15.80 so high 16."""
    inv, low, high = nn.yarn_inv_freq(64, 500000.0, 64.0, 4096,
                                      beta_fast=64.0, beta_slow=1.0)
    assert (low, high) == (5, 16)
    f = lambda i: 500000.0 ** (-2.0 * i / 64)     # noqa: E731
    assert inv[0] == 1.0                        # i <= low: untouched
    np.testing.assert_allclose(inv[5], f(5), rtol=1e-12)
    mid = 10                                    # ramp (10 - 5) / 11
    r = (mid - 5) / 11
    np.testing.assert_allclose(inv[mid], f(mid) / 64 * r + f(mid) * (1 - r),
                               rtol=1e-12)
    np.testing.assert_allclose(inv[16], f(16) / 64, rtol=1e-12)
    np.testing.assert_allclose(inv[31], f(31) / 64, rtol=1e-12)
    assert math.isclose(f(31), 500000.0 ** (-62 / 64))


def test_rope_tables_partial_and_scaled():
    cos, sin = nn.rope_tables(
        16, 128, rope_theta=500000, rope_type="yarn", factor=64,
        original_max_position_embeddings=4096, beta_slow=1, beta_fast=64,
        attention_factor=1.4158883083359672, partial_rotary_factor=0.5)
    assert cos.shape == sin.shape == (16, 64) and cos.dtype == jnp.float32
    np.testing.assert_allclose(cos[0], 1.4158883083359672, rtol=1e-6)
    np.testing.assert_allclose(sin[3, 0], 1.4158883083359672 * math.sin(3.0),
                               rtol=1e-6)
    np.testing.assert_allclose(cos[:, :32], cos[:, 32:])
    cos, sin = nn.rope_tables(8, 128, rope_theta=10000, rope_type="default",
                              partial_rotary_factor=1)
    assert cos.shape == (8, 128)
    np.testing.assert_allclose(sin[5, 1], math.sin(5 * 10000 ** (-2 / 128)),
                               rtol=1e-6)


def test_rope_rotates_the_first_part_of_a_head_and_passes_the_rest():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((1, 4, 2, 8)).astype(np.float32)
    ang = rng.uniform(0, 3, (4, 2)).astype(np.float32)
    ang = np.concatenate([ang, ang], axis=1)        # rot 4 of 8
    out = ops.rope_rotate_half(pt.to_tensor(x), pt.to_tensor(np.cos(ang)),
                               pt.to_tensor(np.sin(ang))).numpy()
    np.testing.assert_array_equal(out[..., 4:], x[..., 4:])
    for i in range(2):      # the pair (i, i + 2) turns by its angle
        a, c = x[0, :, :, i], x[0, :, :, i + 2]
        th = ang[:, i][:, None]
        np.testing.assert_allclose(out[0, :, :, i],
                                   a * np.cos(th) - c * np.sin(th), atol=1e-6)
        np.testing.assert_allclose(out[0, :, :, i + 2],
                                   c * np.cos(th) + a * np.sin(th), atol=1e-6)
