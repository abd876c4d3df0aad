"""`flash_attention(window=)`: the kernels (run by the interpreter)
forward and backward against the masked XLA composite, for windows
smaller than, equal to and larger than a block and the sequence, with
fewer key/value heads than query heads; the count of visited tiles; and
that no window is today's program."""
from importlib import import_module

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = import_module("paddle_tpu.kernels.pallas.flash_attention")
D = 128


def _operands(b, s, H, Hk, seed=0):
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)
    return (draw(b, s, H, D, scale=0.5), draw(b, s, Hk, D, scale=0.5),
            draw(b, s, Hk, D), draw(b, s, H, D))


CASES = [
    # seq, H, Hk, window, block_q, block_k
    (512, 2, 1, 64, 128, 256),      # inside a walk's sub-block
    (512, 2, 1, 128, 128, 256),     # a q block wide
    (512, 4, 2, 200, 128, 128),     # straddles blocks, 4 k blocks
    (1024, 2, 1, 512, 256, 256),    # Laguna's, four k blocks
    (1024, 2, 1, 256, 256, 256),    # a block exactly
    (1024, 8, 1, 300, 256, 1024),   # one k block holds the sequence
    (1024, 2, 2, 1024, 256, 512),   # the sequence exactly
    (1024, 2, 1, 2000, 256, 512),   # longer than the sequence: causal
]


@pytest.mark.parametrize("s,H,Hk,window,bq,bk", CASES)
def test_window_kernels_match_the_masked_composite(s, H, Hk, window, bq, bk):
    b = 1
    q, k, v, g = _operands(b, s, H, Hk)
    scale = D ** -0.5
    ref, vjp = jax.vjp(lambda q, k, v: fa._xla_attention(
        q, k, v, None, True, scale, window=window), q, k, v)
    rq, rk, rv = vjp(g)
    qm, km, vm = (q.reshape(b, s, H * D), k.reshape(b, s, Hk * D),
                  v.reshape(b, s, Hk * D))
    kw = dict(block_q=bq, block_k=bk, interpret=True, Hk=Hk,
              sm_scale=scale, window=window, autotune_ok=False)
    o, lse = fa._flash_fwd_fused(qm, km, vm, H, True, **kw)
    dq, dk, dv = fa._flash_bwd_fused(qm, km, vm, o, lse,
                                     g.reshape(b, s, H * D), H, True, **kw)
    for got, want in ((o, ref), (dq, rq), (dk, rk), (dv, rv)):
        np.testing.assert_allclose(got.reshape(want.shape), want,
                                   atol=3e-5, rtol=1e-4)


def test_the_composite_window_is_the_band_by_hand():
    s, w = 16, 4
    q, k, v, _ = _operands(1, s, 2, 1)
    got = fa._xla_attention(q, k, v, None, True, 1.0, window=w)
    i = np.arange(s)
    band = (i[:, None] >= i[None]) & (i[:, None] - i[None] < w)
    kk = jnp.repeat(k, 2, axis=2)
    vv = jnp.repeat(v, 2, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kk)
    p = jax.nn.softmax(jnp.where(band, sc, -jnp.inf), axis=-1)
    np.testing.assert_allclose(
        got, jnp.einsum("bhqk,bkhd->bqhd", p, vv), atol=1e-5)


def test_a_window_needs_causal():
    q, k, v, _ = _operands(1, 128, 2, 1)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, causal=False, window=8)


def test_flash_attention_takes_the_window_off_the_tpu_too():
    q, k, v, g = _operands(1, 256, 4, 2)
    out = fa.flash_attention(q, k, v, causal=True, window=32)
    want = fa._xla_attention(q, k, v, None, True, D ** -0.5, window=32)
    np.testing.assert_allclose(out, want, atol=1e-6)
    assert not np.allclose(
        out, fa.flash_attention(q, k, v, causal=True), atol=1e-3)


@pytest.mark.parametrize("sq,bq,bk,sub,window,visited", [
    (8192, 256, 512, 256, 512, 93),     # Laguna's forward: 3 tiles a q block
    (8192, 256, 1024, 256, 512, 93),    # and backward
    (8192, 256, 512, 256, None, 528),   # causal alone
    (1024, 256, 256, 256, 2000, 10),    # a window past the sequence
    (512, 128, 256, 256, 64, 5),
])
def test_causal_tiles_counts_the_window(sq, bq, bk, sub, window, visited):
    assert fa.causal_tiles(sq, sq, bq, bk, sub, True, window) == (
        visited, (sq // bq) * (sq // sub))
    if window is None or window >= sq:
        assert fa.causal_tiles(sq, sq, bq, bk, sub, True) == (
            visited, (sq // bq) * (sq // sub))


def test_a_windowed_grid_holds_only_the_blocks_in_sight():
    assert fa._window_kblocks(256, 512, 512, 16) == 3
    assert fa._window_kblocks(256, 1024, 512, 8) == 2
    assert fa._window_qblocks(256, 1024, 512, 32) == 7
    assert fa._window_kblocks(256, 512, 100000, 16) == 16


def _lowered(window_kw):
    q = jnp.zeros((1, 512, 2, D), jnp.bfloat16)
    k = jnp.zeros((1, 512, 1, D), jnp.bfloat16)

    def loss(q, k, v):
        return fa._flash_core((q, k, v), None, True, 1.0, True,
                              *window_kw).astype(jnp.float32).sum()
    # the traced program, kernels' bodies and grids included (lowering a
    # Mosaic kernel needs a TPU target: tests/test_tpu_aot_compile.py)
    return str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, k))


def test_no_window_traces_the_program_it_was():
    """`window=None` is no operand, no second kernel body and no other
    grid: the call lowers to the same text as one that never heard of a
    window, and a window changes it."""
    plain = _lowered(())
    assert _lowered((None,)) == plain
    assert "window" not in plain
    assert _lowered((128,)) != plain
