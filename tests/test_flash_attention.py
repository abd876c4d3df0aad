"""Pallas flash-attention kernel conformance.

Runs the real kernel logic on CPU via pallas interpret mode
(pl.pallas_call(interpret=True)) so CI exercises the blockwise
forward AND the FA2-style backward without TPU hardware; a TPU-gated
test covers the compiled path. Mirrors the reference's
test/legacy_test/test_flash_attention.py (composite-vs-fused check).

Kernels use the fused-head layout [b, s, h*d]; tests drive them through
the same wrappers the dispatch path uses.
"""
import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.core.mesh_plan import mesh_plan

fa = importlib.import_module("paddle_tpu.kernels.pallas.flash_attention")


def _make(b=2, s=256, h=2, d=64, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    return q, k, v


def _fuse(x):
    b, s, h, d = x.shape
    return x.reshape(b, s, h * d)


def _interp_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    h = q.shape[2]
    o, lse = fa._flash_fwd_fused(_fuse(q), _fuse(k), _fuse(v), h, causal,
                                 block_q=block_q, block_k=block_k,
                                 interpret=True, sm_scale=sm_scale)
    return o, lse, (_fuse(q), _fuse(k), _fuse(v))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256)])
def test_fwd_interpret_matches_composite(causal, block_q, block_k):
    q, k, v = _make()
    sc = 1.0 / np.sqrt(q.shape[-1])
    o, _, _ = _interp_fwd(q, k, v, sc, causal, block_q, block_k)
    ref = fa._xla_attention(q, k, v, None, causal, sc)
    np.testing.assert_allclose(np.asarray(o), np.asarray(_fuse(ref)),
                               rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_lse_matches_composite(causal):
    q, k, v = _make()
    b, s, h, d = q.shape
    sc = 1.0 / np.sqrt(d)
    _, lse, _ = _interp_fwd(q, k, v, sc, causal, 128, 128)
    sco = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                     k.astype(jnp.float32)) * sc
    if causal:
        qpos = jnp.arange(s)[:, None]
        kpos = jnp.arange(s)[None, :]
        sco = jnp.where(qpos >= kpos, sco, fa._NEG_INF)
    ref = jax.scipy.special.logsumexp(sco, axis=-1)      # [b, h, sq]
    got = lse.reshape(b, h, fa._SUBL, s)
    np.testing.assert_allclose(np.asarray(got[:, :, 0]), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # replicated across the sublane tile
    np.testing.assert_array_equal(np.asarray(got[:, :, 0]),
                                  np.asarray(got[:, :, -1]))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s,block_q,block_k",
                         [(256, 128, 128), (256, 128, 256),
                          (384, 128, 256), (384, 256, 128)])
def test_bwd_interpret_matches_composite(causal, s, block_q, block_k):
    q, k, v = _make(s=s)
    b, _, h, d = q.shape
    sc = 1.0 / np.sqrt(d)
    o, lse, (qm, km, vm) = _interp_fwd(q, k, v, sc, causal,
                                       block_q, block_k)
    rng = np.random.default_rng(1)
    do = jnp.asarray(rng.standard_normal(o.shape), o.dtype)
    dq, dk, dv = fa._flash_bwd_fused(qm, km, vm, o, lse, do, h, causal,
                                     block_q=block_q, block_k=block_k,
                                     interpret=True, sm_scale=sc)

    def comp(qm, km, vm):
        qh = qm.reshape(b, s, h, d)
        kh = km.reshape(b, s, h, d)
        vh = vm.reshape(b, s, h, d)
        sco = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * sc
        if causal:
            qpos = jnp.arange(s)[:, None]
            kpos = jnp.arange(s)[None, :]
            sco = jnp.where(qpos >= kpos, sco, fa._NEG_INF)
        p = jax.nn.softmax(sco, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vh).reshape(b, s, h * d)

    _, vjp = jax.vjp(comp, _fuse(q), km, vm)
    rq, rk, rv = vjp(do)
    for got, ref in ((dq, rq), (dk, rk), (dv, rv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4)


def test_nonsquare_block_pick():
    # seq 384: block picker must fall back to a divisor (384 = 3*128)
    assert fa._pick_block(384, 512) == 384
    assert fa._pick_block(384, 256) == 128
    assert fa._pick_block(1024, 512) == 512
    q, k, v = _make(s=384)
    sc = 1.0 / np.sqrt(q.shape[-1])
    o, _, _ = _interp_fwd(q, k, v, sc, True, 256, 256)
    ref = fa._xla_attention(q, k, v, None, True, sc)
    np.testing.assert_allclose(np.asarray(o), np.asarray(_fuse(ref)),
                               rtol=5e-5, atol=5e-5)


def test_attention_path_gating():
    # CPU backend -> xla; masked -> xla; odd shapes -> xla. Each fallback
    # carries a human-readable reason (VERDICT r2 weak #3).
    path, why = fa.attention_path((2, 256, 4, 64), (2, 256, 4, 64))
    assert path == "xla" and "backend" in why
    path, why = fa.attention_path((2, 256, 4, 64), (2, 256, 4, 64),
                                  masked=True)
    assert path == "xla" and "attn_mask" in why
    path, _ = fa.attention_path((2, 100, 4, 64), (2, 100, 4, 64))
    assert path == "xla"
    # fused-head lane alignment: h*d must be a multiple of 128
    assert not fa._shapes_ok((2, 256, 3, 64), (2, 256, 3, 64))
    assert fa._shapes_ok((2, 256, 4, 64), (2, 256, 4, 64))
    assert fa._shapes_ok((2, 1024, 12, 64), (2, 1024, 12, 64))
    # GQA: kv heads must divide q heads with hk*d lane-aligned
    assert fa._shapes_ok((2, 256, 4, 64), (2, 256, 2, 64))
    assert fa._shapes_ok((2, 1024, 12, 128), (2, 1024, 4, 128))
    assert not fa._shapes_ok((2, 256, 4, 64), (2, 256, 3, 64))
    assert not fa._shapes_ok((2, 256, 8, 64), (2, 256, 1, 64))  # 64 lanes
    assert fa._shapes_ok((2, 256, 8, 128), (2, 256, 1, 128))    # MQA ok


def _xla_ref(q, k, v, causal, sc, segment_ids=None):
    return fa._xla_attention(q, k, v, None, causal, sc,
                             segment_ids=segment_ids)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hk", [1, 2])
def test_gqa_fwd_bwd_interpret(causal, hk):
    """GQA/MQA: q-head h reads kv-head h // (H//Hk) in-kernel."""
    h, d, s, b = 4, 128, 256, 2
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)
    sc = 1.0 / np.sqrt(d)
    qs = (q * sc).astype(q.dtype).reshape(b, s, h * d)
    km, vm = k.reshape(b, s, hk * d), v.reshape(b, s, hk * d)
    o, lse = fa._flash_fwd_fused(qs, km, vm, h, causal, block_q=128,
                                 block_k=128, interpret=True, Hk=hk)
    ref = _xla_ref(q, k, v, causal, sc)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(ref.reshape(b, s, h * d)),
                               rtol=5e-5, atol=5e-5)
    do = jnp.asarray(rng.standard_normal(o.shape), o.dtype)
    dq, dk, dv = fa._flash_bwd_fused(qs, km, vm, o, lse, do, h, causal,
                                     block_q=128, block_k=128,
                                     interpret=True, Hk=hk)
    dq = dq * sc

    def comp(qm, km, vm):
        out = _xla_ref(qm.reshape(b, s, h, d), km.reshape(b, s, hk, d),
                       vm.reshape(b, s, hk, d), causal, sc)
        return out.reshape(b, s, h * d)

    _, vjp = jax.vjp(comp, q.reshape(b, s, h * d), km, vm)
    rq, rk, rv = vjp(do)
    for got, ref_g in ((dq, rq), (dk, rk), (dv, rv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_g),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_segment_ids_fwd_bwd_interpret(causal):
    """Padding + packed-varlen masking via segment ids stays in-kernel."""
    b, s, h, d = 2, 256, 2, 64
    rng = np.random.default_rng(11)
    q, k, v = _make(b=b, s=s, h=h, d=d, seed=11)
    # batch 0: two packed sequences + tail padding; batch 1: all one segment
    seg0 = np.concatenate([np.zeros(100), np.ones(80),
                           -np.ones(76)]).astype(np.int32)
    seg1 = np.zeros(s, np.int32)
    seg = jnp.asarray(np.stack([seg0, seg1]))
    sc = 1.0 / np.sqrt(d)
    qs = (q * sc).astype(q.dtype).reshape(b, s, h * d)
    km, vm = _fuse(k), _fuse(v)
    o, lse = fa._flash_fwd_fused(qs, km, vm, h, causal, block_q=128,
                                 block_k=128, interpret=True,
                                 segment_ids=(seg, seg))
    ref = _xla_ref(q, k, v, causal, sc, segment_ids=(seg, seg))
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(_fuse(ref)),
                               rtol=5e-5, atol=5e-5)
    do = jnp.asarray(rng.standard_normal(o.shape), o.dtype)
    dq, dk, dv = fa._flash_bwd_fused(qs, km, vm, o, lse, do, h, causal,
                                     block_q=128, block_k=128,
                                     interpret=True,
                                     segment_ids=(seg, seg))
    dq = dq * sc

    def comp(qm, km, vm):
        out = _xla_ref(qm.reshape(b, s, h, d), km.reshape(b, s, h, d),
                       vm.reshape(b, s, h, d), causal, sc,
                       segment_ids=(seg, seg))
        return out.reshape(b, s, h * d)

    _, vjp = jax.vjp(comp, _fuse(q), km, vm)
    rq, rk, rv = vjp(do)
    for got, ref_g in ((dq, rq), (dk, rk), (dv, rv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_g),
                                   rtol=5e-4, atol=5e-4)


def test_cross_length_causal_bottom_right():
    """sq != sk causal is bottom-right aligned (FA2 semantics, ADVICE r2):
    the LAST q row sees all sk keys."""
    b, h, d = 1, 2, 64
    sq, sk = 128, 256
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sk, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sk, h, d)), jnp.float32)
    sc = 1.0 / np.sqrt(d)
    qs = (q * sc).astype(q.dtype).reshape(b, sq, h * d)
    o, _ = fa._flash_fwd_fused(qs, k.reshape(b, sk, h * d),
                               v.reshape(b, sk, h * d), h, True,
                               block_q=128, block_k=128, interpret=True)
    ref = _xla_ref(q, k, v, True, sc)  # composite also bottom-right
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(ref.reshape(b, sq, h * d)),
                               rtol=5e-5, atol=5e-5)
    # semantic spot-check vs an explicit bottom-right mask
    s_full = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float64),
                       np.asarray(k, np.float64)) * sc
    qpos = (sk - sq) + np.arange(sq)[:, None]
    kpos = np.arange(sk)[None, :]
    s_full = np.where(qpos >= kpos, s_full, -1e30)
    p = np.exp(s_full - s_full.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    exp = np.einsum("bhqk,bkhd->bqhd", p, np.asarray(v, np.float64))
    np.testing.assert_allclose(
        np.asarray(o).reshape(b, sq, h, d), exp, rtol=1e-4, atol=1e-4)


def test_flash_attn_unpadded_varlen():
    """Packed varlen wrapper == per-sequence dense attention."""
    import paddle_tpu as paddle
    from paddle_tpu.nn.functional import flash_attn_unpadded

    h, d = 2, 64
    lens = [100, 80, 50]
    total = 256  # padded to a 128 multiple
    rng = np.random.default_rng(17)
    qkv = [jnp.asarray(rng.standard_normal((total, h, d)), jnp.float32)
           for _ in range(3)]
    cu = np.cumsum([0] + lens).astype(np.int32)
    out, _ = flash_attn_unpadded(
        paddle.to_tensor(qkv[0]), paddle.to_tensor(qkv[1]),
        paddle.to_tensor(qkv[2]), cu_seqlens_q=cu, cu_seqlens_k=cu,
        causal=True)
    out = np.asarray(out.numpy())
    sc = 1.0 / np.sqrt(d)
    for i in range(len(lens)):
        s0, s1 = cu[i], cu[i + 1]
        qi = qkv[0][None, s0:s1]
        ki = qkv[1][None, s0:s1]
        vi = qkv[2][None, s0:s1]
        ref = _xla_ref(qi, ki, vi, True, sc)[0]
        np.testing.assert_allclose(out[s0:s1], np.asarray(ref),
                                   rtol=5e-4, atol=5e-4)


def test_flash_attention_dispatch_cpu_fallback():
    # public entry must agree with the composite on CPU (xla path)
    q, k, v = _make(s=128)
    out = fa.flash_attention(q, k, v, causal=True)
    ref = fa._xla_attention(q, k, v, None, True,
                           1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the causal walk: key sub-blocks inside the resident block, up to the
# diagonal
# ---------------------------------------------------------------------------
def _walk_case(b, sq, sk, h, hk, d, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sk, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sk, hk, d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((b, sq, h * d)), jnp.float32)
    return q, k, v, do


def _walk_against_composite(q, k, v, do, sub, block_q=256, fwd_block_k=1024,
                            bwd_block_k=512, segment_ids=None):
    """Forward, log-sum and the three gradients of the causal kernels at
    sub-block width `sub` (the module's `_WALK`), each against the XLA
    composite."""
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    sc = 1.0 / np.sqrt(d)
    qm = q.reshape(b, sq, h * d)
    km, vm = k.reshape(b, sk, hk * d), v.reshape(b, sk, hk * d)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "_WALK", sub)
        o, lse = fa._flash_fwd_fused(qm, km, vm, h, True, block_q=block_q,
                                     block_k=fwd_block_k, interpret=True,
                                     Hk=hk, segment_ids=segment_ids,
                                     sm_scale=sc)
        dq, dk, dv = fa._flash_bwd_fused(
            qm, km, vm, o, lse, do, h, True, block_q=block_q,
            block_k=bwd_block_k, interpret=True, Hk=hk,
            segment_ids=segment_ids, sm_scale=sc)

    def comp(qm, km, vm):
        return _xla_ref(qm.reshape(q.shape), km.reshape(k.shape),
                        vm.reshape(v.shape), True, sc,
                        segment_ids=segment_ids).reshape(b, sq, h * d)

    ref, vjp = jax.vjp(comp, q.reshape(b, sq, h * d), km, vm)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=5e-5, atol=5e-5)
    for got, want in zip((dq, dk, dv), vjp(do)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-4)
    # the log-sum of every row that sees a key
    sco = jnp.einsum("bqhd,bkhd->bhqk", q,
                     jnp.repeat(k, h // hk, axis=2)) * sc
    ok = ((sk - sq) + jnp.arange(sq)[:, None]
          >= jnp.arange(sk)[None, :])[None, None]
    if segment_ids is not None:
        ok = ok & (segment_ids[0][:, None, :, None]
                   == segment_ids[1][:, None, None, :])
    want = jax.scipy.special.logsumexp(
        jnp.where(ok, sco, -jnp.inf), axis=-1)           # [b, h, sq]
    got = lse.reshape(b, h, fa._SUBL, sq)[:, :, 0]
    seen = np.broadcast_to(np.asarray(ok.any(-1)), want.shape)
    np.testing.assert_allclose(np.asarray(got)[seen],
                               np.asarray(want)[seen], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("h,hk,d", [(2, 2, 64), (1, 1, 128), (1, 1, 256),
                                    (4, 2, 64), (3, 3, 64)])
@pytest.mark.parametrize("sub", [128, 256, 512])
def test_walk_one_key_block_a_row(sub, h, hk, d):
    """GPT-2 small's shape: sequence 1024 under a 1024-wide K/V block,
    so the forward's only way to skip the masked half is the walk. Two
    heads of 64 are one 128-lane slab, unless they are grouped on one
    key/value head or odd in number (then every head keeps its own
    slice); a head of 128 or 256 is a slab of its own."""
    q, k, v, do = _walk_case(1, 1024, 1024, h, hk, d)
    _walk_against_composite(q, k, v, do, sub)


def test_walk_with_skipped_grid_steps():
    """Sequence 2048: the forward's second key block and the backward's
    early q blocks do no work, and their clamped index maps name a
    neighbour's block."""
    q, k, v, do = _walk_case(1, 2048, 2048, 2, 2, 64, seed=1)
    _walk_against_composite(q, k, v, do, 256)


@pytest.mark.parametrize("sq,sk", [(384, 768), (640, 384)])
def test_walk_cross_length_bottom_right(sq, sk):
    """sq != sk: the diagonal is bottom-right aligned and its offset
    (384, -256) is no multiple of the sub-block; with sq > sk the first
    rows see no key at all."""
    q, k, v, do = _walk_case(1, sq, sk, 2, 2, 64, seed=2)
    _walk_against_composite(q, k, v, do, 256, block_q=128,
                            fwd_block_k=768, bwd_block_k=384)


@pytest.mark.parametrize("sub", [128, 256])
def test_walk_with_segment_ids(sub):
    b, s = 2, 512
    q, k, v, do = _walk_case(b, s, s, 2, 2, 64, seed=3)
    seg0 = np.concatenate([np.zeros(200), np.ones(180),
                           -np.ones(132)]).astype(np.int32)
    seg = jnp.asarray(np.stack([seg0, np.zeros(s, np.int32)]))
    _walk_against_composite(q, k, v, do, sub, segment_ids=(seg, seg))


def test_walk_multi_query_20_to_1():
    """Jamba's attention layer: 20 query heads on one key/value head."""
    q, k, v, do = _walk_case(1, 512, 512, 20, 1, 128, seed=4)
    _walk_against_composite(q, k, v, do, 256)


def _parent_fwd_noncausal(q, k, v, H, block_q, block_k):
    """The forward kernel as it stood before the walk, without causality:
    whole-block body, statistics one column a head."""
    from jax.experimental import pallas as pl
    b, sq, HD = q.shape
    sk, D = k.shape[1], HD // H

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref):
        ki, nk = pl.program_id(2), pl.num_programs(2)

        @pl.when(ki == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, fa._NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)

        qf, kf, vf = q_ref[0], k_ref[0], v_ref[0]
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            s = jax.lax.dot_general(qf[:, sl], kf[:, sl],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            m_prev = m_ref[:, h:h + 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:, h:h + 1] = alpha * l_ref[:, h:h + 1] + jnp.sum(
                p, axis=1, keepdims=True)
            acc_ref[:, sl] = acc_ref[:, sl] * alpha + jax.lax.dot_general(
                p.astype(vf.dtype), vf[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:, h:h + 1] = m_new

        @pl.when(ki == nk - 1)
        def _finalize():
            l = l_ref[:]
            for h in range(H):
                sl = slice(h * D, (h + 1) * D)
                o_ref[0, :, sl] = (acc_ref[:, sl] / l[:, h:h + 1]).astype(
                    o_ref.dtype)
            lse_ref[0] = m_ref[:] + jnp.log(l)

    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, grid=(b, sq // block_q, sk // block_k),
        in_specs=[pl.BlockSpec((1, block_q, HD), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, block_k, HD), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, block_k, HD), lambda b, i, j: (b, j, 0))],
        out_specs=[pl.BlockSpec((1, block_q, HD), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, block_q, fa._LANES),
                                lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, sq, HD), q.dtype),
                   jax.ShapeDtypeStruct((b, sq, fa._LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, HD), jnp.float32),
                        pltpu.VMEM((block_q, fa._LANES), jnp.float32),
                        pltpu.VMEM((block_q, fa._LANES), jnp.float32)],
        interpret=True)(q, k, v)


@pytest.mark.parametrize("d,dtype", [(64, jnp.float32), (128, jnp.bfloat16)])
def test_noncausal_forward_is_the_parents_bit_for_bit(d, dtype):
    """Without causality there is nothing to walk: one whole-block visit,
    and the same float operations in the same order as before. Output and
    log-sum equal the earlier kernel's to the bit (the statistics only
    moved from one column a head to every lane)."""
    h = 256 // d
    q, k, v = (_fuse(x) for x in _make(b=1, s=512, h=h, d=d, dtype=dtype,
                                       seed=5))
    o, lse = fa._flash_fwd_fused(q, k, v, h, False, block_q=128,
                                 block_k=256, interpret=True)
    o_was, lse_was = _parent_fwd_noncausal(q, k, v, h, 128, 256)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_was))
    got = lse.reshape(1, h, fa._SUBL, 512)[:, :, 0]          # [b, h, s]
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(lse_was[:, :, :h]).transpose(0, 2, 1))


@pytest.mark.parametrize("sq,sk,block_k,sub,causal,want", [
    (1024, 1024, 1024, 256, True, (10, 16)),    # GPT-2 small, forward
    (1024, 1024, 512, 256, True, (10, 16)),     # ... and backward
    (2048, 2048, 1024, 256, True, (36, 64)),    # GPT-3 1.3B, forward
    (4096, 4096, 1024, 256, True, (136, 256)),  # Jamba's attention layer
    (1024, 1024, 1024, 512, True, (6, 8)),
    (384, 768, 768, 256, True, (8, 9)),         # bottom-right, bq 128
    (1024, 1024, 1024, 256, False, (4, 4)),     # no causality: the total
])
def test_causal_tiles(sq, sk, block_k, sub, causal, want):
    block_q = 128 if sq == 384 else 256
    assert fa.causal_tiles(sq, sk, block_q, block_k, sub,
                           causal=causal) == want


def test_compile_record_says_what_the_flash_kernels_visit(monkeypatch):
    """`compile_record("train_step")["flash_causal"]`: of the [s, s]
    score tiles, how many each kernel of the traced step visits."""
    from paddle_tpu.observability import perf
    step = _tiny_gpt_step(monkeypatch, "plain")
    perf._FAMILY_COMPILE.pop("train_step", None)
    ids = np.zeros((1, 512), np.int32)
    assert np.isfinite(float(step(ids, ids).numpy()))
    assert perf.compile_record("train_step")["flash_causal"] == (
        "fwd 3/4 of 256-wide tiles; bwd 3/4 of 256-wide tiles, dq whole")


def _tiny_gpt_step(monkeypatch, variant):
    """A one-layer GPT `TrainStep` on the interpreted kernels: plain, with
    a (zero-length) cache handed to every layer, or under a two-device
    mesh."""
    import paddle_tpu as pt
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.optimizer import AdamW
    _interpreted_kernels(monkeypatch)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_AUTOTUNE", "0")
    pt.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=128, num_layers=1, num_heads=2,
        max_position_embeddings=512, use_flash_attention=True))
    model.train()
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        if variant != "cache":
            return crit(m(ids), labels)
        none = pt.zeros([ids.shape[0], 0, 2, 64])
        return crit(m(ids, caches=[(none, none)])[0], labels)

    mesh = {}
    if variant == "mesh":
        mesh = dict(mesh=Mesh(np.array(jax.devices()[:2]), ("dp",)),
                    shard_data=P("dp", None))
    return TrainStep(model, AdamW(learning_rate=1e-3,
                                  parameters=model.parameters()),
                     loss_fn, **mesh)


@pytest.mark.parametrize("variant,want", [
    ("plain", "qkv in place"), ("cache", "split"), ("mesh", "split")])
def test_compile_record_says_where_the_kernels_read_qkv(monkeypatch, variant,
                                                        want):
    """`compile_record("train_step")["flash_operands"]`: the kernels of a
    GPT step read q, k, v in `qkv_proj`'s output; with a cache or under
    a `mesh_plan` the projection is split into [b, s, h, d] as before."""
    from paddle_tpu.observability import perf
    step = _tiny_gpt_step(monkeypatch, variant)
    perf._FAMILY_COMPILE.pop("train_step", None)
    ids = np.zeros((2, 512), np.int32)
    assert np.isfinite(float(step(ids, ids).numpy()))
    assert perf.compile_record("train_step")["flash_operands"] == want


def test_nothing_stands_between_qkv_proj_and_the_kernels(monkeypatch):
    """In the traced step both kernels take `qkv_proj`'s output itself,
    three times: its dot and bias, and no slice, transpose or reshape;
    the forward's `o` and the backward's `do` are what `out_proj` reads
    and returns, three-dimensional, and `delta` is made in the kernel
    from the two."""
    step = _tiny_gpt_step(monkeypatch, "plain")
    ids = jnp.zeros((2, 512), jnp.int32)
    jaxpr = step._step_fn.jit_fn.trace(
        step.params, step.opt_states, step.buffers, jax.random.PRNGKey(0),
        jnp.float32(1e-3), [ids, ids], {}).jaxpr.jaxpr
    made_by = {v: e for e in jaxpr.eqns for v in e.outvars}
    kernels = {e.params["name"]: e for e in jaxpr.eqns
               if e.primitive.name == "pallas_call"}
    assert sorted(kernels) == ["flash_bwd_transpose", "flash_fwd"]
    for call in kernels.values():
        q, k, v = call.invars[:3]
        assert q is k is v and q.aval.shape == (2, 512, 3 * 128)
        chain = []
        while chain[-1:] != ["dot_general"]:
            chain.append(made_by[q].primitive.name)
            q = made_by[q].invars[0]
        assert chain == ["add", "dot_general"]          # bias, product
    o = kernels["flash_fwd"].outvars[0]
    assert o.aval.shape == (2, 512, 128)
    # its name for a checkpoint policy (an identity), and nothing else
    (named,) = [e for e in jaxpr.eqns if o in e.invars]
    assert (named.primitive.name, named.params["name"]) == ("name",
                                                            fa.FLASH_O)
    o = named.outvars[0]
    readers = [e.primitive.name for e in jaxpr.eqns if o in e.invars]
    assert "reshape" not in readers and "dot_general" in readers
    o_again, do = kernels["flash_bwd_transpose"].invars[3:5]
    assert o_again is o and do.aval.shape == (2, 512, 128)
    assert made_by[do].primitive.name == "dot_general"   # out_proj's dx


def _qkv_case(h, d, dtype, seed=0, b=2, s=256):
    rng = np.random.default_rng(seed)
    qkv = jnp.asarray(rng.standard_normal((b, s, 3 * h * d)), dtype)
    g = jnp.asarray(rng.standard_normal((b, s, h * d)), dtype)
    seg = np.zeros((b, s), np.int32)
    seg[0, 100:180], seg[0, 180:] = 1, -1
    return qkv, g, jnp.asarray(seg)


@pytest.mark.parametrize("h,d", [(4, 64), (2, 128)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_seg", [False, True])
def test_qkv_in_place_is_the_split_entry_bit_for_bit(monkeypatch, h, d,
                                                     causal, with_seg):
    """The fused entry against the [b, s, h, d] entry on the same
    numbers: the same tiles reach the same kernel bodies, so `o`, `lse`
    and the gradient (the concatenation of the split entry's three) are
    equal to the bit, the scale applied in the kernels on both sides."""
    _interpreted_kernels(monkeypatch)
    qkv, g, seg = _qkv_case(h, d, jnp.bfloat16)
    b, s, _ = qkv.shape
    seg = (seg, seg) if with_seg else None
    sc = 1.0 / np.sqrt(d)
    o, res = fa._flash_core_fwd((qkv,), seg, causal, sc, True, None, h)
    (dqkv,), _ = fa._flash_core_bwd(causal, sc, True, None, h, res, g)
    q, k, v = (x.reshape(b, s, h, d) for x in jnp.split(qkv, 3, axis=2))
    o4, res4 = fa._flash_core_fwd((q, k, v), seg, causal, sc, True)
    grads, _ = fa._flash_core_bwd(causal, sc, True, None, None, res4,
                                  g.reshape(b, s, h, d))
    np.testing.assert_array_equal(np.asarray(o),
                                  np.asarray(o4.reshape(b, s, h * d)))
    np.testing.assert_array_equal(np.asarray(res[2]), np.asarray(res4[2]))
    np.testing.assert_array_equal(
        np.asarray(dqkv), np.asarray(jnp.concatenate(
            [x.reshape(b, s, h * d) for x in grads], axis=2)))
    # and the public entries agree with the composite
    want = fa._xla_attention(q, k, v, None, causal, sc, segment_ids=seg)
    np.testing.assert_allclose(
        np.asarray(o, np.float32),
        np.asarray(want.reshape(b, s, h * d), np.float32),
        rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("h,d", [(4, 64), (2, 128)])
def test_dq_whole_against_dq_in_partials(h, d):
    """One K/V block over the key sequence: dq leaves the kernel summed
    over the walk, scaled and rounded once, and the call has no partial
    array; with two blocks it writes [b, 2, sq, HD] partials that XLA
    sums into dq's columns of the one gradient array. Both within the
    file's tolerance of each other, dk and dv too (their accumulation
    order does not change)."""
    qkv, do, _ = _qkv_case(h, d, jnp.float32, seed=1, b=1)
    sc = 1.0 / np.sqrt(d)
    kw = dict(interpret=True, sm_scale=sc, cols=(0, 1, 2), D=d)
    o, lse = fa._flash_fwd_fused(qkv, qkv, qkv, h, True, **kw)

    def bwd(block_k):
        return lambda qkv, do: fa._flash_bwd_fused(
            qkv, qkv, qkv, o, lse, do, h, True, block_k=block_k, **kw)

    def kernel_outputs(block_k):
        eqns = jax.make_jaxpr(bwd(block_k))(qkv, do).jaxpr.eqns
        call, = [e for e in eqns if e.primitive.name == "pallas_call"]
        return ([v.aval.shape for v in call.outvars],
                [e.primitive.name for e in eqns])

    hd = h * d
    shapes, prims = kernel_outputs(None)
    assert shapes == [(1, 256, 3 * hd)] and prims == ["pallas_call"]
    shapes, prims = kernel_outputs(128)
    assert shapes == [(1, 256, 3 * hd), (1, 2, 256, hd)]
    assert "add" in prims and "dynamic_update_slice" in prims
    np.testing.assert_allclose(np.asarray(bwd(None)(qkv, do)),
                               np.asarray(bwd(128)(qkv, do)),
                               rtol=5e-4, atol=5e-4)
    # three arrays: dq, dk, dv, and dq's partials when there are any
    q, k, v = jnp.split(qkv, 3, axis=2)
    for block_k, first in ((None, (1, 256, hd)), (128, (1, 2, 256, hd))):
        eqns = jax.make_jaxpr(lambda q, k, v, do: fa._flash_bwd_fused(
            q, k, v, o, lse, do, h, True, block_k=block_k, interpret=True,
            sm_scale=sc))(q, k, v, do).jaxpr.eqns
        call, = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert [x.aval.shape for x in call.outvars] == [
            first, (1, 256, hd), (1, 256, hd)]


@pytest.mark.parametrize("h,d", [(4, 64), (2, 128)])
def test_scale_in_the_kernels_is_the_scale_before_them(h, d):
    """q scaled where its block is loaded, in float32 and rounded to the
    operand dtype once, is `(q * sm_scale).astype(q.dtype)` handed to
    unscaling kernels: the forward equal to the bit in bf16; dq, scaled
    where it is rounded, equal to the bit in float32 (in bf16 it is
    rounded once where scaling afterwards rounds twice)."""
    sc = 1.0 / np.sqrt(d)              # numpy's float64, as the entries'
    for dtype in (jnp.bfloat16, jnp.float32):
        qkv, do, _ = _qkv_case(h, d, dtype, seed=2, b=1)
        q, k, v = jnp.split(qkv, 3, axis=2)
        qs = (q * sc).astype(dtype)
        o, lse = fa._flash_fwd_fused(q, k, v, h, True, interpret=True,
                                     sm_scale=sc)
        o_was, lse_was = fa._flash_fwd_fused(qs, k, v, h, True,
                                             interpret=True)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(o_was))
        np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse_was))
    got = fa._flash_bwd_fused(q, k, v, o, lse, do, h, True, interpret=True,
                              sm_scale=sc)
    was = fa._flash_bwd_fused(qs, k, v, o, lse, do, h, True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(was[0] * sc))
    for a, b in zip(got[1:], was[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gpt_attention_is_the_same_through_both_entries(monkeypatch, dtype):
    """`GPTAttention` on the fused entry against the same layer on the
    [b, s, h, d] entry (what a mesh plan or a cache makes it take): the
    same output and the same parameter gradients."""
    import contextlib
    import paddle_tpu as pt
    from paddle_tpu.autograd import tape
    from paddle_tpu.jit import _functional_params
    from paddle_tpu.models.gpt import GPTAttention, GPTConfig
    _interpreted_kernels(monkeypatch)
    pt.seed(0)
    layer = GPTAttention(GPTConfig(
        vocab_size=256, hidden_size=256, num_layers=1, num_heads=2,
        max_position_embeddings=256, use_flash_attention=True))
    tensors = list(layer.parameters())
    params = [p._data.astype(dtype) for p in tensors]
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 256, 256)),
                    dtype)
    one_device = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",))

    def loss(params, x, planned):
        plan = mesh_plan(one_device) if planned else (
            contextlib.nullcontext())
        with _functional_params(tensors, params), tape.no_grad(), plan:
            out = layer(pt.to_tensor(x))._data
        return (out.astype(jnp.float32) ** 2).sum(), out

    (_, out), grads = jax.value_and_grad(loss, has_aux=True)(
        params, x, False)
    (_, out4), grads4 = jax.value_and_grad(loss, has_aux=True)(
        params, x, True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out4, np.float32),
                               rtol=tol, atol=tol)
    for g, g4 in zip(grads, grads4):
        scale = float(jnp.abs(g4.astype(jnp.float32)).max())
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(g4, np.float32),
                                   rtol=tol, atol=tol * scale)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Pallas path needs TPU")
def test_fwd_bwd_tpu_compiled():
    q, k, v = _make(s=512, dtype=jnp.bfloat16)
    sc = 1.0 / np.sqrt(q.shape[-1])

    def f_p(q, k, v):
        return (_ := fa._flash_core((q, k, v), None, True, sc, True)).astype(
            jnp.float32).sum()

    def f_x(q, k, v):
        return fa._xla_attention(q, k, v, None, True, sc).astype(
            jnp.float32).sum()

    gp = jax.grad(f_p, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(f_x, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gx):
        rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
        assert rel < 1e-2, rel


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Pallas path needs TPU")
def test_bwd_tpu_bf16_multi_kblock_partials():
    # seq 2048 -> multiple k-blocks -> the dq partial-sum path runs with
    # bf16-quantized partials; bound the added rounding error vs XLA
    q, k, v = _make(b=1, s=2048, h=2, dtype=jnp.bfloat16, seed=3)
    sc = 1.0 / np.sqrt(q.shape[-1])

    def f_p(q, k, v):
        return fa._flash_core((q, k, v), None, True, sc, True).astype(
            jnp.float32).sum()

    def f_x(q, k, v):
        return fa._xla_attention(q, k, v, None, True, sc).astype(
            jnp.float32).sum()

    dq_p = jax.grad(f_p)(q, k, v)
    dq_x = jax.grad(f_x)(q, k, v)
    rel = float(jnp.abs(dq_p - dq_x).max() / (jnp.abs(dq_x).max() + 1e-9))
    assert rel < 2e-2, rel


# ---------------------------------------------------------------------------
# no probe, no fallback; and the kernel under a mesh
# ---------------------------------------------------------------------------
def test_tpu_backend_kernel_failure_raises_not_falls_back(monkeypatch):
    """On a tpu backend the Pallas kernel IS the path: when it cannot
    compile (here: a Mosaic kernel handed to the CPU compiler) the error
    reaches the caller. It used to be caught by a probe that latched the
    XLA composite in, with nothing but a missing speed-up to show it."""
    q, k, v = _make()
    assert not fa._pallas_available()           # off tpu: no, untried
    assert fa.attention_path(q.shape, k.shape)[0] == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("PADDLE_TPU_PALLAS_AUTOTUNE", "0")
    assert fa.attention_path(q.shape, k.shape) == ("pallas", "")
    with pytest.raises(Exception) as exc:
        jax.block_until_ready(fa.flash_attention(q, k, v, causal=True))
    assert "interpret" in str(exc.value).lower() \
        or "pallas" in str(exc.value).lower()
    # shape-based routing is a decision from something the code can
    # see, and stays: an odd length still takes the composite
    odd = _make(s=100)
    out = fa.flash_attention(*odd, causal=True)
    assert out.shape == odd[0].shape


def _interpreted_kernels(monkeypatch):
    """Steer the dispatch onto the Pallas kernels on the CPU test box:
    the backend check answers "tpu" and the two kernels run in
    interpret mode."""
    import functools
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_flash_fwd_fused", functools.partial(
        fa._flash_fwd_fused, interpret=True))
    monkeypatch.setattr(fa, "_flash_bwd_fused", functools.partial(
        fa._flash_bwd_fused, interpret=True))


@pytest.mark.parametrize("batch_axes,h,want", [
    (("dp",), 4, (("dp",), None, ("mp",), None)),   # Megatron layout
    ((), 8, (None, None, ("dp", "mp"), None)),      # heads over all 4
    (("dp",), 2, (("dp",), None, None, None)),      # 1 head x 64: whole
])
def test_mesh_plan_splits_kernel_with_shard_map(monkeypatch, batch_axes, h,
                                                want):
    """Under `mesh_plan` the kernel is split over the mesh (batch over
    the data axes, heads over the others where the per-device heads
    still fit the kernel) and gives the unsplit kernel's outputs and
    gradients."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    _interpreted_kernels(monkeypatch)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    q, k, v = _make(b=2, s=128, h=h, d=64, seed=3)
    spec, seg_spec = fa._planned_specs((mesh, batch_axes), q.shape, k.shape)
    assert spec == P(*want)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True)
        return (out * out).sum(), out

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))
    ref_g, ref_out = grad(q, k, v)
    sh = NamedSharding(mesh, P("dp"))
    with mesh_plan(mesh, batch_axes):
        got_g, got_out = jax.jit(
            jax.grad(loss, argnums=(0, 1, 2), has_aux=True),
            in_shardings=(sh, sh, sh))(q, k, v)
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(ref_out),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(got_g, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
