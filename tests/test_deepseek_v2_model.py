"""`models/deepseek_v2.py`, `nn.MultiHeadLatentAttention`, the flash
core with a key in two parts, the router's weights as scored and the
sequence-wise balance term, on the CPU at a tiny size: latent attention
against its equations and against plain attention on the assembled
keys, the two-part kernels (interpret mode) against the composite with
all five gradients, the shared key's gradient as the sum over heads,
causality, what the kernel refuses by the name of the part, the router
against its equations beside Laguna's and Qwen3-Next's as they were, the
balance term's formula and where its gradient goes, the share read from
a configuration's dict, what the model refuses by name, `recompute`, and
what a `TrainStep(has_aux=True)` step hands out and notes. The program
against the plain reference is `benchmarks/tests/test_deepseek_v2.py`."""
import math
from importlib import import_module

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, ops
from paddle_tpu.incubate.nn.functional import causal_attention
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import (DeepseekV2Config, DeepseekV2ForCausalLM,
                               DeepseekV2PretrainingCriterion,
                               deepseek_v2_tiny)
from paddle_tpu.models.deepseek_v2 import yarn_mscale
from paddle_tpu.observability import perf
from paddle_tpu.optimizer import AdamW

fa = import_module("paddle_tpu.kernels.pallas.flash_attention")
HI = jax.lax.Precision.HIGHEST


def _batch(rows=2, seq=32, vocab=512, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, (rows, seq + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def _randomise(layer, seed=0, scale=0.3):
    rng = np.random.default_rng(seed)
    for _n, p in layer.named_parameters():
        p._data = pt.to_tensor((rng.standard_normal(p.shape) * scale)
                               .astype(np.float32))._data


def _operands(b, s, H, D, R, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = ((b, s, H, D), (b, s, H, R), (b, s, H, D), (b, s, 1, R),
              (b, s, H, D), (b, s, H, D))
    return [jax.random.normal(k, sh, dtype) for k, sh in zip(ks, shapes)]


def _plain(q, q_pe, k, k_pe, v, scale, causal=True):
    """Softmax attention on keys assembled at D + R a head."""
    qf = jnp.concatenate([q, q_pe], -1)
    kf = jnp.concatenate([k, jnp.broadcast_to(
        k_pe, k.shape[:3] + k_pe.shape[3:])], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf, precision=HI) * scale
    if causal:
        n = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                      precision=HI)


# -- the model's description -------------------------------------------------
def test_the_published_defaults_are_deepseek_v2_lite():
    c = DeepseekV2Config()
    assert (c.hidden_size, c.num_hidden_layers, c.vocab_size) == (
        2048, 27, 102400)
    assert (c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, c.kv_lora_rank, c.q_lora_rank) == (
        16, 128, 64, 128, 512, None)
    assert (c.n_routed_experts, c.num_experts_per_tok, c.n_shared_experts,
            c.moe_intermediate_size, c.intermediate_size) == (
        64, 6, 2, 1408, 10944)
    assert c.norm_topk_prob is False and c.seq_aux is True
    assert c.experts_held == (0, 64)
    # 192^(-1/2) x mscale(40, 0.707)^2
    assert yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert c.softmax_scale == pytest.approx(0.11472, abs=1e-5)


def test_layer_0_is_dense_and_the_rest_sparse():
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny(num_hidden_layers=4))
    kinds = ["moe" if hasattr(lay, "moe") else "mlp"
             for lay in model.model.layers]
    assert kinds == ["mlp", "moe", "moe", "moe"]
    for lay in model.model.layers:
        assert isinstance(lay.attn, nn.MultiHeadLatentAttention)
    moe = model.model.layers[1].moe
    assert moe.router.score == "softmax"
    assert moe.router.more == {"normalize": False, "with_scores": True}
    # the two shared experts are one feed-forward of 2 x the width
    assert moe.shared_expert.gate_proj.weight.shape == [64, 64]
    names = [n for n, _p in model.named_parameters()]
    assert names[1:7] == [
        "model.layers.0.input_layernorm.weight",
        "model.layers.0.attn.q_proj.weight",
        "model.layers.0.attn.kv_a_proj_with_mqa.weight",
        "model.layers.0.attn.kv_a_layernorm.weight",
        "model.layers.0.attn.kv_b_proj.weight",
        "model.layers.0.attn.o_proj.weight"]


@pytest.mark.parametrize("bad,named", [
    (dict(q_lora_rank=1536), "q_lora_rank 1536"),
    (dict(n_group=8, topk_group=3, topk_method="group_limited_greedy"),
     "group-limited routing"),
    (dict(scoring_func="sigmoid"), "scoring_func 'sigmoid'"),
    (dict(moe_layer_freq=2), "moe_layer_freq 2"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(rope_scaling={"type": "linear", "factor": 2}),
     "rope_scaling type 'linear'"),
])
def test_what_the_model_cannot_run_is_refused_by_name(bad, named):
    with pytest.raises(NotImplementedError, match=named):
        deepseek_v2_tiny(**bad)


def test_from_dict_reads_a_share():
    d = dict(vocab_size=100, hidden_size=64, n_routed_experts=8,
             num_hidden_layers=3, published={"n_routed_experts": 64},
             expert_first=16, unknown_key=1,
             seeded_draws={"residual_output": 0.01})
    c = DeepseekV2Config.from_dict(d, recompute=True)
    assert c.n_routed_experts == 64 and c.experts_held == (16, 8)
    assert c.out_std == 0.01 and c.recompute and c.vocab_size == 100
    whole = DeepseekV2Config.from_dict(dict(n_routed_experts=16))
    assert whole.experts_held == (0, 16)


# -- latent attention ---------------------------------------------------------
def test_latent_attention_is_its_equations():
    """The layer against the equations written out in numpy-like jnp: the
    split of q, the latent and its norm, the up-projection's split, the
    rotary on the rotary parts alone, ONE shared key head, the scale."""
    cfg = deepseek_v2_tiny()
    H, dn, dr, dv, rank = 4, 16, 8, 16, 32
    layer = nn.MultiHeadLatentAttention(
        64, H, dn, dr, dv, rank, 1e-6, cfg.softmax_scale)
    _randomise(layer, 1)
    b, s = 2, 24
    u = np.random.default_rng(2).standard_normal((b, s, 64)).astype("f4")
    cos, sin = cfg.rope_table(s)
    got = layer(pt.to_tensor(u), cos, sin).numpy()

    p = {n: np.asarray(t.numpy(), np.float64)
         for n, t in layer.named_parameters()}
    u64 = u.astype(np.float64)
    q = (u64 @ p["q_proj.weight"]).reshape(b, s, H, dn + dr)
    ckv = u64 @ p["kv_a_proj_with_mqa.weight"]
    c, k_pe = ckv[..., :rank], ckv[..., rank:].reshape(b, s, 1, dr)
    c = c / np.sqrt((c ** 2).mean(-1, keepdims=True) + 1e-6) \
        * p["kv_a_layernorm.weight"]
    kv = (c @ p["kv_b_proj.weight"]).reshape(b, s, H, dn + dv)

    def turn(x):
        co, si = (np.asarray(t, np.float64)[None, :, None, :]
                  for t in (cos, sin))
        half = np.concatenate([-x[..., dr // 2:], x[..., :dr // 2]], -1)
        return x * co + half * si

    o = _plain(*(jnp.asarray(a, jnp.float32) for a in (
        q[..., :dn], turn(q[..., dn:]), kv[..., :dn], turn(k_pe),
        kv[..., dn:])), cfg.softmax_scale)
    want = np.asarray(o, np.float64).reshape(b, s, H * dv) \
        @ p["o_proj.weight"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("use_flash", [False, True])
def test_the_one_attention_call_takes_a_key_in_two_parts(use_flash):
    q, q_pe, k, k_pe, v, _g = _operands(2, 40, 4, 16, 8)
    t = [pt.to_tensor(np.asarray(x)) for x in (q, q_pe, k, k_pe, v)]
    for scale in (None, 0.3):
        got = causal_attention(t[0], t[2], t[4], use_flash,
                               shared=(t[1], t[3]), scale=scale).numpy()
        want = _plain(q, q_pe, k, k_pe, v,
                      24 ** -0.5 if scale is None else scale)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    with pytest.raises(NotImplementedError, match="two parts"):
        causal_attention(t[0], t[2], t[4], use_flash, scale=0.3)


@pytest.mark.parametrize("H,D,causal,blocks", [
    (4, 128, True, ((128, 256), (128, 256))),    # dq in two partials
    (2, 128, False, ((128, 256), (128, 256))),
    (4, 64, True, ((128, 256), (128, 512))),     # heads of 64, dq whole
])
def test_the_two_part_kernels_agree_with_the_composite(H, D, causal, blocks):
    """Interpret mode: the forward and all five gradients, the shared
    head's summed over the heads."""
    *xs, g = _operands(1, 512, H, D, 64, seed=3)
    scale = 0.11
    want, vjp = jax.vjp(lambda *x: fa._xla_attention(
        *fa._whole_heads(x), None, causal, scale), *xs)
    rows = fa._shared_rows(xs)
    o, lse = fa._flash_fwd_shared(*rows, H, D, 64, causal, scale,
                                  interpret=True, blocks=blocks[0])
    np.testing.assert_allclose(o.reshape(want.shape), want, atol=2e-5)
    got = fa._shared_grads(*fa._flash_bwd_shared(
        *rows, o, lse, g.reshape(o.shape), H, D, 64, causal, scale,
        interpret=True, blocks=blocks[1]), H, D, 64)
    for name, a, b in zip(("dq", "dq'", "dk", "dk'", "dv"), got, vjp(g)):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=3e-5, err_msg=name)


def test_the_shared_keys_gradient_is_the_sum_over_heads():
    """The core's gradient to k' [b, s, 1, r] against the sum of the
    gradients H separate one-head attentions give their own copies."""
    q, q_pe, k, k_pe, v, g = _operands(1, 32, 4, 16, 8, seed=5)

    def core(q, q_pe, k, k_pe, v):
        return fa.flash_attention(q, k, v, causal=True, softmax_scale=0.2,
                                  shared=(q_pe, k_pe))

    _o, vjp = jax.vjp(core, q, q_pe, k, k_pe, v)
    d_shared = vjp(g)[3]
    assert d_shared.shape == (1, 32, 1, 8)
    total = 0.0
    for h in range(4):
        sl = slice(h, h + 1)
        _o, one = jax.vjp(core, q[:, :, sl], q_pe[:, :, sl], k[:, :, sl],
                          k_pe, v[:, :, sl])
        total = total + one(g[:, :, sl])[3]
    np.testing.assert_allclose(d_shared, total, rtol=1e-5, atol=1e-6)
    with pytest.raises(NotImplementedError, match="no mask, segment ids"):
        fa.flash_attention(q, k, v, causal=True, window=8,
                           shared=(q_pe, k_pe))


def test_a_change_at_a_position_moves_no_output_before_it():
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny())
    _randomise(model, 3, 0.1)
    model.eval()
    ids, _ = _batch(1, 24)
    base = model(pt.to_tensor(ids)).numpy()
    ids2 = ids.copy()
    ids2[0, 15] = (ids2[0, 15] + 7) % 512
    moved = model(pt.to_tensor(ids2)).numpy()
    np.testing.assert_array_equal(base[:, :15], moved[:, :15])
    assert np.abs(base[:, 15:] - moved[:, 15:]).max() > 1e-4


@pytest.mark.parametrize("shared,v_dim,named", [
    (((1, 256, 4, 32), (1, 256, 1, 32)), 128, "one shared head of 64"),
    (((1, 256, 4, 64), (1, 256, 4, 64)), 128, "one shared head of 64"),
    (((1, 256, 4, 64), (1, 256, 1, 64)), 64, "value part 64"),
])
def test_a_shape_the_kernel_refuses_names_the_part(monkeypatch, shared,
                                                   v_dim, named):
    """`attention_path` says why, by the part at fault, and the
    functional op's warning carries it."""
    q = (1, 256, 4, 128)
    v = (1, 256, 4, v_dim)
    assert named in fa._shape_reject_reason(q, q, shared, v)
    assert fa._shape_reject_reason(
        q, q, ((1, 256, 4, 64), (1, 256, 1, 64)), q) is None
    assert "even number" in fa._shape_reject_reason(
        (1, 256, 3, 128), (1, 256, 3, 128),
        ((1, 256, 3, 64), (1, 256, 1, 64)), (1, 256, 3, 128))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    path, why = fa.attention_path(q, q, shared=shared, v_shape=v)
    assert path == "xla" and named in why
    from paddle_tpu.incubate.nn import functional as F
    with pytest.warns(RuntimeWarning, match=named):
        F._warn_if_composite(q, q, shared, v)
    assert fa.attention_path(
        q, q, shared=((1, 256, 4, 64), (1, 256, 1, 64)),
        v_shape=q) == ("pallas", "")


# -- the router and the balance term --------------------------------------
def test_the_routers_weights_are_the_softmaxs_own_values():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((12, 16)).astype("f4")
    w = rng.standard_normal((16, 8)).astype("f4")
    weights, experts, scores = ops.moe_route(
        pt.to_tensor(x), pt.to_tensor(w), 3, 1.0, "softmax",
        normalize=False, with_scores=True)
    z = x.astype(np.float64) @ w.astype(np.float64)
    soft = np.exp(z - z.max(-1, keepdims=True))
    soft /= soft.sum(-1, keepdims=True)
    np.testing.assert_allclose(scores.numpy(), soft, rtol=1e-5)
    order = np.argsort(-soft, -1)[:, :3]
    np.testing.assert_array_equal(experts.numpy(), order)
    np.testing.assert_allclose(weights.numpy(),
                               np.take_along_axis(soft, order, -1),
                               rtol=1e-5)
    assert (weights.numpy().sum(-1) < 1).all()


@pytest.mark.parametrize("score,scale", [("sigmoid", 2.5), ("softmax", 1.0)])
def test_the_older_routers_weights_are_what_they_were(score, scale):
    """Laguna's (sigmoid, scaled) and Qwen3-Next's (softmax): the chosen
    scores normalised to one, times the scale, two outputs."""
    rng = np.random.default_rng(1)
    x = pt.to_tensor(rng.standard_normal((12, 16)).astype("f4"))
    w = pt.to_tensor(rng.standard_normal((16, 8)).astype("f4"))
    out = ops.moe_route(x, w, 3, scale, score)
    assert len(out) == 2
    np.testing.assert_allclose(out[0].numpy().sum(-1), scale, rtol=1e-5)
    _w, _e, scores = ops.moe_route(x, w, 3, scale, score, with_scores=True)
    top = np.sort(scores.numpy(), -1)[:, ::-1][:, :3]
    np.testing.assert_allclose(out[0].numpy(),
                               scale * top / top.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_the_balance_term_is_its_formula():
    rng = np.random.default_rng(2)
    rows, T, E, K = 3, 20, 8, 2
    s = rng.random((rows, T, E)).astype("f4")
    s /= s.sum(-1, keepdims=True)
    chosen = np.argsort(-s, -1)[..., :K].astype(np.int32)
    got = ops.moe_sequence_balance(
        pt.to_tensor(s.reshape(-1, E)), pt.to_tensor(chosen.reshape(-1, K)),
        rows).numpy()
    want = 0.0
    for r in range(rows):
        f = np.bincount(chosen[r].ravel(), minlength=E) * E / (K * T)
        want += (f * s[r].mean(0)).sum() / rows
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # an even load reads 1
    even = np.full((1, E, E), 1.0 / E, "f4")
    turn = (np.arange(E)[:, None] + np.arange(K)[None]) % E
    assert ops.moe_sequence_balance(
        pt.to_tensor(even[0]), pt.to_tensor(turn.astype(np.int32)),
        1).numpy() == pytest.approx(1.0)


def test_the_balance_terms_gradient_reaches_the_router_only():
    """Of a sparse layer's leaves only the router's weight has a
    gradient from the balance term (and the input, through it)."""
    layer = nn.SparseExpertFFN(32, 16, num_experts=8, top_k=2, held=(0, 4),
                               shared_width=16, routed_scale=1.0,
                               router_score="softmax",
                               router_normalize=False,
                               aux="sequence_balance")
    _randomise(layer, 4)
    x = np.random.default_rng(5).standard_normal((2, 10, 32)).astype("f4")
    names = [n for n, _p in layer.named_parameters()]

    def term(params, x):
        for (_n, p), value in zip(layer.named_parameters(), params):
            p._data = value
        return layer(pt.to_tensor(x))[2]._data

    params = [p._data for _n, p in layer.named_parameters()]
    grads, dx = jax.grad(term, argnums=(0, 1))(params, jnp.asarray(x))
    moved = {n for n, g in zip(names, grads) if float(jnp.abs(g).max()) > 0}
    assert moved == {"router.weight"}
    assert float(jnp.abs(dx).max()) > 0


def test_only_the_linear_routers_hand_out_a_balance_term():
    with pytest.raises(ValueError, match="balance"):
        nn.SparseExpertFFN(32, 16, num_experts=8, top_k=1,
                           router_mlp=(8, (0.1, 0.1, 0.1)),
                           aux="sequence_balance")
    with pytest.raises(ValueError, match="aux 'load'"):
        nn.SparseExpertFFN(32, 16, num_experts=8, aux="load")


def test_the_criterion_adds_alpha_times_the_mean_term():
    crit = DeepseekV2PretrainingCriterion(0.5)
    logits = pt.to_tensor(np.random.default_rng(0).standard_normal(
        (2, 6, 11)).astype("f4"))
    labels = pt.to_tensor(np.random.default_rng(1).integers(
        0, 11, (2, 6)).astype(np.int32))
    plain, zero = crit(logits, labels)
    loss, balance = crit(logits, labels,
                         pt.to_tensor(np.array([1.0, 1.5], "f4")))
    assert float(zero.numpy()) == 0.0
    assert float(balance.numpy()) == pytest.approx(1.25)
    assert float(loss.numpy()) == pytest.approx(
        float(plain.numpy()) + 0.5 * 1.25, rel=1e-6)


# -- the step -----------------------------------------------------------------
def _step(recompute, flash=False, lr=1e-3):
    pt.seed(0)
    cfg = deepseek_v2_tiny(recompute=recompute, use_flash_attention=flash,
                           experts_held=(4, 8))
    model = DeepseekV2ForCausalLM(cfg)
    model.train()
    crit = DeepseekV2PretrainingCriterion(cfg.aux_loss_alpha)

    def loss_fn(m, ids, labels):
        loss, balance = crit(m(ids), labels, m.balance_terms)
        return loss, (m.expert_counts, balance)

    return TrainStep(model, AdamW(learning_rate=lr,
                                  parameters=model.parameters()),
                     loss_fn, has_aux=True)


def test_gradients_agree_with_and_without_recompute():
    """The first step's gradients as the optimizer got them (its first
    moment is (1 - beta1) times them) and two steps' losses: plain
    layers on the composite against recomputed layers on the flash
    entry. (The parameters after AdamW are no measure: it makes steps of
    the rate's size of gradients that are rounding noise.)"""
    ids, labels = _batch()
    a, b = _step(False), _step(True, flash=True)
    np.testing.assert_allclose(a(ids, labels).numpy(),
                               b(ids, labels).numpy(), rtol=1e-6)
    for sa, sb in zip(a.opt_states, b.opt_states):
        ga, gb = np.asarray(sa["moment1"]), np.asarray(sb["moment1"])
        np.testing.assert_allclose(ga, gb, rtol=1e-3,
                                   atol=1e-5 * np.abs(ga).max() + 1e-12)
    np.testing.assert_allclose(a(ids, labels).numpy(),
                               b(ids, labels).numpy(), rtol=1e-5)


def test_a_step_hands_out_the_counts_and_the_balance_term_and_notes():
    ids, labels = _batch()
    step = _step(True, flash=True)
    loss = float(step(ids, labels).numpy())
    counts, balance = (np.asarray(a) for a in step.aux)
    assert math.isfinite(loss)
    assert counts.shape == (2, 8) and counts.dtype == np.int32
    # 64 tokens x 4 choices over 16 experts, half of them held
    assert 0 < counts.sum(1).min() and counts.sum(1).max() < 64 * 4
    assert 0.9 < float(balance) < 1.6
    notes = perf.compile_record("train_step")
    assert notes["attention"].startswith("xla: no TPU Pallas backend")
    assert notes["moe"].endswith(
        "softmax scores, weights as scored, sequence balance term")
    assert notes["flash_kept"] == ("o and lse kept across recompute in 3 "
                                   "of 3 recomputed layers")
    assert notes["rope"].startswith("composite")
