"""Ragged paged attention kernel conformance.

The jnp reference path is the engine's CPU tier-1 / oracle
implementation; it is checked here against a from-first-principles
naive construction (per-token python loops over the ownership map),
and the Pallas kernel logic runs on CPU via interpret mode against the
reference — mirroring tests/test_flash_attention.py. A TPU-gated test
covers the compiled path.

Scenario shapes follow the engine's layout contract (module docstring
of kernels/pallas/ragged_paged_attention.py): token-major pools,
off[row, physical_page] = start position (-1 unowned), rows=-1 dead
padding.
"""
import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ra = importlib.import_module(
    "paddle_tpu.kernels.pallas.ragged_paged_attention")


def _naive(q, k_new, v_new, kpool, vpool, rows, pos, kv_start, off,
           bs, scale, kdq=None, vdq=None, with_pool=True):
    """Per-token loop oracle: pool context strictly below kv_start via
    the ownership map, then own-row causal packed context."""
    q, k_new, v_new = (np.asarray(a, np.float64) for a in
                      (q, k_new, v_new))
    kpool = np.asarray(kpool, np.float64)
    vpool = np.asarray(vpool, np.float64)
    T, H, D = q.shape
    Hk = k_new.shape[1]
    G = H // Hk
    out = np.zeros((T, H, D))
    for t in range(T):
        r = int(rows[t])
        if r < 0:
            continue
        for h in range(H):
            hk = h // G
            ks, vs = [], []
            if with_pool:
                for p in range(off.shape[1]):
                    st = int(off[r, p])
                    if st < 0:
                        continue
                    for s in range(bs):
                        if st + s < kv_start[r]:
                            kk = kpool[p * bs + s, hk]
                            vv = vpool[p * bs + s, hk]
                            if kdq is not None:
                                kk = kk * float(kdq[hk])
                            if vdq is not None:
                                vv = vv * float(vdq[hk])
                            ks.append(kk)
                            vs.append(vv)
            for u in range(T):
                if int(rows[u]) == r and pos[u] <= pos[t]:
                    ks.append(k_new[u, hk])
                    vs.append(v_new[u, hk])
            s_ = np.array([q[t, h] @ kk * scale for kk in ks])
            p_ = np.exp(s_ - s_.max())
            p_ = p_ / p_.sum()
            out[t, h] = sum(pp * vv for pp, vv in zip(p_, vs))
    return out


def _mixed_case(T=64, B=4, NB=8, bs=8, H=4, Hk=2, D=64, int8=False,
                seed=0, scatter_pages=False):
    """One packed launch with every row kind the engine ships:
    row 0 fresh prefill (no pool reads), row 1 single decode token,
    row 2 a verify window, row 3 a prefix-resume suffix; tail dead."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((T, H, D)).astype(np.float32) * 0.3
    k_new = rng.standard_normal((T, Hk, D)).astype(np.float32) * 0.3
    v_new = rng.standard_normal((T, Hk, D)).astype(np.float32) * 0.3
    if int8:
        kpool = rng.integers(-127, 128, (NB * bs, Hk, D)).astype(np.int8)
        vpool = rng.integers(-127, 128, (NB * bs, Hk, D)).astype(np.int8)
        kdq = (rng.uniform(0.01, 0.05, (Hk,))).astype(np.float32)
        vdq = (rng.uniform(0.01, 0.05, (Hk,))).astype(np.float32)
    else:
        kpool = rng.standard_normal((NB * bs, Hk, D)).astype(
            np.float32) * 0.3
        vpool = rng.standard_normal((NB * bs, Hk, D)).astype(
            np.float32) * 0.3
        kdq = vdq = None
    rows = np.full((T,), -1, np.int32)
    pos = np.zeros((T,), np.int32)
    kv_start = np.zeros((B,), np.int32)
    off = np.full((B, NB), -1, np.int32)
    c = 0

    def pack(r, start, m):
        nonlocal c
        rows[c:c + m] = r
        pos[c:c + m] = start + np.arange(m)
        kv_start[r] = start
        c += m

    pack(0, 0, 20)               # fresh prefill, 20 tokens
    pack(1, 24, 1)               # decode, 24 cached tokens
    pack(2, 10, 5)               # verify window over 10 cached
    pack(3, 16, 7)               # prefix-resume over 16 cached
    # physical pages: row 1 -> pages 0..2, row 2 -> 3..4, row 3 -> 5..6
    # (scatter_pages: the same seven drawn from anywhere in the pool)
    phys = rng.permutation(NB)[:7] if scatter_pages else np.arange(7)
    off[1, phys[0:3]] = np.arange(3) * bs
    off[2, phys[3:5]] = np.arange(2) * bs
    off[3, phys[5:7]] = np.arange(2) * bs
    return dict(q=q, k_new=k_new, v_new=v_new, kpool=kpool,
                vpool=vpool, rows=rows, pos=pos, kv_start=kv_start,
                off=off, bs=bs, scale=1.0 / np.sqrt(D), kdq=kdq,
                vdq=vdq)


def _run_ref(c, path="jnp", with_pool=True):
    return np.asarray(ra.ragged_paged_attention(
        jnp.asarray(c["q"]), jnp.asarray(c["k_new"]),
        jnp.asarray(c["v_new"]), jnp.asarray(c["kpool"]),
        jnp.asarray(c["vpool"]), jnp.asarray(c["rows"]),
        jnp.asarray(c["pos"]), jnp.asarray(c["kv_start"]),
        jnp.asarray(c["off"]), block_size=c["bs"], scale=c["scale"],
        kdq=None if c["kdq"] is None else jnp.asarray(c["kdq"]),
        vdq=None if c["vdq"] is None else jnp.asarray(c["vdq"]),
        with_pool=with_pool, path=path))


def test_reference_matches_naive_mixed_rows():
    c = _mixed_case()
    got = _run_ref(c)
    ref = _naive(**c)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_reference_int8_pool_dequant():
    c = _mixed_case(int8=True)
    got = _run_ref(c)
    ref = _naive(**c)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_reference_no_pool_is_packed_causal_self_attention():
    c = _mixed_case()
    got = _run_ref(c, with_pool=False)
    ref = _naive(**{**c, "with_pool": False})
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_dead_rows_emit_zero():
    c = _mixed_case()
    got = _run_ref(c)
    dead = np.asarray(c["rows"]) < 0
    assert dead.any()
    np.testing.assert_array_equal(got[dead], 0.0)
    assert np.isfinite(got).all()


def test_gqa_and_mqa_head_mapping():
    for hk in (1, 2):
        c = _mixed_case(Hk=hk, D=128, seed=3)
        got = _run_ref(c)
        ref = _naive(**c)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("int8", [False, True])
def test_pallas_interpret_matches_reference(int8):
    # D=128 keeps Hk*D lane-aligned so the kernel shape is accepted
    c = _mixed_case(Hk=2, D=128, int8=int8, seed=5)
    assert ra._shape_reject_reason(
        64, c["kpool"].shape[0], 4, 2, 128, c["bs"], True) is None
    got = _run_ref(c, path="pallas_interpret")
    ref = _run_ref(c, path="jnp")
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
    dead = np.asarray(c["rows"]) < 0
    np.testing.assert_array_equal(got[dead], 0.0)


@pytest.mark.parametrize("NB,block_k", [
    (9, 24),       # 3 pages per pool tile: not a power of two
    (40, 16),      # 20 pool tiles inside one lane group of voff
    (256, 512),    # 64 pages per tile: two tiles per lane group, 2 groups
])
def test_pallas_interpret_pool_tiles_and_lane_groups(NB, block_k):
    """The in-kernel ownership mask across several pool tiles, with the
    owned pages scattered over the pool so every tile/lane-group offset
    of the page-count operand is exercised."""
    c = _mixed_case(NB=NB, Hk=2, D=128, seed=13, scatter_pages=True)
    args = [jnp.asarray(c[k]) for k in (
        "q", "k_new", "v_new", "kpool", "vpool", "rows", "pos",
        "kv_start", "off")]
    got = np.asarray(ra._ragged_pallas(
        *args, c["bs"], c["scale"], interpret=True, block_k=block_k,
        autotune_ok=False))
    ref = _run_ref(c, path="jnp")
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_pallas_interpret_no_pool():
    c = _mixed_case(Hk=2, D=128, seed=7)
    got = _run_ref(c, path="pallas_interpret", with_pool=False)
    ref = _run_ref(c, path="jnp", with_pool=False)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_path_gating_and_shape_rejects():
    # CPU backend -> jnp with a human-readable reason
    path, why = ra.ragged_attention_path(64, 64, 4, 2, 128, 8)
    if jax.default_backend() != "tpu":
        assert path == "jnp" and "backend" in why
    # token stream must stay sublane/lane-aligned
    assert "multiple of 8" in ra._shape_reject_reason(
        12, 64, 4, 2, 128, 8, True)
    assert "multiple of 128" in ra._shape_reject_reason(
        192, 64, 4, 2, 128, 8, True)
    # head-lane alignment (Hk*D: one 64-wide kv head is 64 lanes)
    assert "lane-aligned" in ra._shape_reject_reason(
        64, 64, 4, 1, 64, 8, True)
    # kv heads must divide q heads
    assert "divide" in ra._shape_reject_reason(
        64, 64, 4, 3, 128, 8, True)
    # pool granularity
    assert "block_size" in ra._shape_reject_reason(
        64, 64, 4, 2, 128, 12, True)
    assert "pool length" in ra._shape_reject_reason(
        64, 60, 4, 2, 128, 8, True)
    # the no-pool variant skips pool-shape checks entirely
    assert ra._shape_reject_reason(
        64, 0, 4, 2, 128, 8, False) is None


def test_pick_div():
    assert ra._pick_div(384, 512, 128) == 384
    assert ra._pick_div(384, 256, 128) == 128
    assert ra._pick_div(64, 256, 8) == 64
    assert ra._pick_div(8, 256, 128) is None


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Pallas path needs TPU")
def test_pallas_compiled_matches_reference_tpu():
    c = _mixed_case(T=256, Hk=2, D=128, seed=11)
    got = _run_ref(c, path="pallas")
    ref = _run_ref(c, path="jnp")
    np.testing.assert_allclose(got, ref, rtol=5e-3, atol=5e-3)


def test_engine_raises_when_its_launch_lands_on_the_reference_on_tpu(
        monkeypatch):
    """Off tpu the dispatcher answers "jnp" without trying anything and
    the engine records why. On a tpu backend the reference is not a
    serving path: a launch shape the engine made itself and the kernel
    cannot take used to be a RuntimeWarning, and is now an error."""
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=1,
                    num_heads=4, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    eng = LLMEngine(GPTForCausalLM(cfg), max_batch=2, block_size=8,
                    num_blocks=8, prompt_quantum=16)
    _fn, path = eng._ragged_fn(16, False, False)
    assert path == "jnp"
    assert "backend" in eng._ragged_paths[("ragged", 16, False, False)][1]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # 4 heads x 16: the fused head axis is 64 lanes, the kernel wants 128
    with pytest.raises(RuntimeError, match="lane-aligned"):
        eng._ragged_fn(32, False, False)
    assert ("ragged", 32, False, False) not in eng._fns
    # and with a shape the kernel takes, the kernel is the path: nothing
    # is tried first, nothing falls back
    assert ra.ragged_attention_path(64, 64, 4, 2, 128, 8) == ("pallas", "")
    c = _mixed_case(Hk=2, D=128)
    with pytest.raises(Exception):
        jax.block_until_ready(_run_ref(c, path=None))
