"""`models/zaya.py`, `nn.CompressedConvAttention` and the MLP router on
the CPU at a tiny size: compressed convolutional attention by its parts,
the depth averaging through `recompute`, one choice a token at the
extremes of the load, the share read from a configuration's dict, what a
`TrainStep(has_aux=True)` step hands out, and the load gauges. The
program against the plain reference is `benchmarks/tests/test_zaya.py`."""
import math

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp, nn, ops
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import (GPTPretrainingCriterion, ZayaConfig,
                               ZayaForCausalLM, zaya_tiny)
from paddle_tpu.optimizer import AdamW

H, HK, D, HIDDEN = 8, 2, 16, 64     # 8 latent heads on 2: groups of 4


def _batch(rows=2, seq=32, vocab=512, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, (rows, seq + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def _attention(seed=0):
    pt.seed(seed)
    layer = nn.CompressedConvAttention(HIDDEN, H, HK, D, std=0.3)
    rng = np.random.default_rng(seed)
    for p in (layer.conv_dw_bias, layer.conv_group_bias, layer.temperature):
        p._data = pt.to_tensor(rng.standard_normal(p.shape).astype(
            np.float32) * 0.5 + 1.0)._data
    return layer


def _mixed(layer, qkv):
    return [t.numpy() for t in ops.cca_mix(
        pt.to_tensor(qkv), layer.conv_dw_weight, layer.conv_dw_bias,
        layer.conv_group_weight, layer.conv_group_bias, layer.temperature,
        H, HK)]


def _qkv(rows=2, seq=12, seed=1):
    return np.random.default_rng(seed).standard_normal(
        (rows, seq, (H + 2 * HK) * D)).astype(np.float32)


# -- the model's description -------------------------------------------------
def test_every_layer_is_cca_and_a_one_choice_sparse_ffn():
    pt.seed(0)
    model = ZayaForCausalLM(zaya_tiny(experts_held=(2, 2)))
    for lay in model.zaya.layers:
        assert lay.attn.qkv_proj.weight.shape == [64, (4 + 2 * 2) * 16]
        assert lay.attn.conv_dw_weight.shape == [2, 6 * 16]
        assert lay.attn.conv_group_weight.shape == [6, 32, 16]
        assert lay.attn.temperature.shape == [2]
        assert lay.attn.o_proj.weight.shape == [4 * 16, 64]
        assert lay.moe.gate_up_proj.shape == [2, 64, 128]
        assert lay.moe.router.down_proj.shape == [64, 32]
        assert lay.moe.router.fc3.shape == [32, 4]     # all 4 experts
        assert lay.moe.shared_expert is None and lay.moe.top_k == 1
        assert lay.attn_res.alpha_o.shape == [64]
    names = [n for n, _p in model.named_parameters()]
    assert "lm_head.weight" not in names            # the head is tied
    assert len(names) == 2 + 24 * 3


def test_the_published_defaults_are_zaya1_8b():
    c = ZayaConfig()
    assert (c.num_hidden_layers, c.hidden_size, c.vocab_size) == (
        40, 2048, 262272)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        8, 2, 128)
    assert (c.cca_time0, c.cca_time1, c.router_hidden_size) == (2, 2, 256)
    assert (c.num_experts, c.num_experts_per_tok) == (16, 1)
    assert c.layer_types == ["hybrid"] * 40
    assert c.rope_parameters["hybrid"]["rope_theta"] == 5000000
    assert tuple(c.experts_held) == (0, 16)
    with pytest.raises(ValueError, match="entries"):
        ZayaConfig(num_hidden_layers=3, layer_types=["hybrid"])
    for unsupported in (dict(tie_word_embeddings=False),
                        dict(num_experts_per_tok=2),
                        dict(layer_types=["hybrid_sliding"]),
                        dict(sliding_window=4096)):
        with pytest.raises(NotImplementedError):
            ZayaConfig(num_hidden_layers=1, **unsupported)
    with pytest.raises(NotImplementedError, match="taps"):
        nn.CompressedConvAttention(64, 4, 2, 16, taps=(4, 2))


def test_from_dict_reads_the_share_of_a_benchmark_configuration():
    d = {"hidden_size": 64, "num_hidden_layers": 2, "num_experts": 2,
         "expert_first": 2, "published": {"num_experts": 4},
         "layer_types": ["hybrid"] * 2, "model_type": "zaya",
         "seeded_draws": {"embedding": 1.0}}
    c = ZayaConfig.from_dict(d, recompute=True)
    assert (c.num_experts, tuple(c.experts_held)) == (4, (2, 2))
    assert c.recompute and c.hidden_size == 64
    whole = ZayaConfig.from_dict({k: v for k, v in d.items()
                                  if k != "published"})
    assert (whole.num_experts, tuple(whole.experts_held)) == (2, (0, 2))


# -- compressed convolutional attention by its parts -------------------------
def test_a_change_at_a_position_moves_no_output_before_it():
    layer = _attention()
    seq, t = 12, 7
    rng = np.random.default_rng(2)
    u = rng.standard_normal((2, seq, HIDDEN)).astype(np.float32)
    cos, sin = nn.rope_tables(seq, D, rope_theta=100.0,
                              partial_rotary_factor=0.5)
    before = layer(pt.to_tensor(u), cos, sin).numpy()
    u[:, t] += 1.0
    after = layer(pt.to_tensor(u), cos, sin).numpy()
    np.testing.assert_array_equal(after[:, :t], before[:, :t])
    # and every later position feels it: the taps, the shifted value
    # head (t + 1) and the attention (all after)
    assert (np.abs(after[:, t:] - before[:, t:]).max(axis=-1) > 1e-6).all()


def test_the_second_value_head_is_the_previous_tokens():
    layer, qkv = _attention(), _qkv()
    _q, _k, v = _mixed(layer, qkv)
    n = (H + HK) * D
    np.testing.assert_array_equal(v[:, :, 0], qkv[:, :, n:n + D])
    np.testing.assert_array_equal(v[:, 1:, 1], qkv[:, :-1, n + D:])
    # before the row's start there is no token and the projections have
    # no bias: the shifted head at position 0 is nothing
    assert not v[:, 0, 1].any()


def test_queries_and_keys_leave_at_length_sqrt_d_times_the_temperature():
    layer, qkv = _attention(), _qkv()
    q, k, _v = _mixed(layer, qkv)
    np.testing.assert_allclose(np.linalg.norm(q, axis=-1), math.sqrt(D),
                               rtol=1e-5)
    tau = layer.temperature.numpy()
    np.testing.assert_allclose(
        np.linalg.norm(k, axis=-1),
        np.broadcast_to(np.abs(tau) * math.sqrt(D), k.shape[:-1]),
        rtol=1e-5)


def test_the_keys_mean_is_the_mean_over_its_four_query_heads():
    """With both convolutions silent (weights and biases zero) a query
    is its head's mean m_h = (q~_h + k~_g) / 2 scaled to length, and a
    key the mean of its group's four m_h."""
    layer, qkv = _attention(), _qkv()
    for p in (layer.conv_dw_weight, layer.conv_dw_bias,
              layer.conv_group_weight, layer.conv_group_bias):
        p._data = p._data * 0
    q, k, _v = _mixed(layer, qkv)
    b, s, _ = qkv.shape
    zq = qkv[..., :H * D].reshape(b, s, H, D)
    zk = qkv[..., H * D:(H + HK) * D].reshape(b, s, HK, D)
    m = (zq + np.repeat(zk, H // HK, axis=2)) / 2
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    np.testing.assert_allclose(q, math.sqrt(D) * unit(m), atol=1e-5)
    mbar = m.reshape(b, s, HK, H // HK, D).mean(axis=3)
    tau = layer.temperature.numpy()[:, None]
    np.testing.assert_allclose(k, math.sqrt(D) * tau * unit(mbar),
                               atol=1e-5)


def test_the_convolutions_are_two_taps_zero_before_the_rows_start():
    """z'' against both convolutions written position by position."""
    layer, qkv = _attention(), _qkv(rows=1, seq=5)
    for p in (layer.temperature,):
        p._data = p._data * 0 + 1
    q, k, _v = _mixed(layer, qkv)
    n = (H + HK) * D
    z = qkv[0, :, :n]
    a, b = layer.conv_dw_weight.numpy(), layer.conv_dw_bias.numpy()
    A, b2 = layer.conv_group_weight.numpy(), layer.conv_group_bias.numpy()
    z1 = np.stack([a[0] * (z[t - 1] if t else 0) + a[1] * z[t] + b
                   for t in range(5)]).reshape(5, H + HK, D)
    z2 = np.stack([
        np.einsum("hc,hcd->hd", z1[t - 1] if t else 0 * z1[0], A[:, :D])
        + np.einsum("hc,hcd->hd", z1[t], A[:, D:]) for t in range(5)]) \
        + b2.reshape(H + HK, D)
    zq = z[:, :H * D].reshape(5, H, D)
    zk = z[:, H * D:].reshape(5, HK, D)
    m = (zq + np.repeat(zk, H // HK, axis=1)) / 2
    want_q = z2[:, :H] + m
    want_q = math.sqrt(D) * want_q / np.linalg.norm(want_q, axis=-1,
                                                    keepdims=True)
    np.testing.assert_allclose(q[0], want_q, atol=2e-5)


def test_the_composite_and_the_flash_entry_agree():
    ids, _ = _batch()
    outs = []
    for flash in (False, True):
        pt.seed(0)
        model = ZayaForCausalLM(zaya_tiny(use_flash_attention=flash))
        model.eval()
        outs.append(model(pt.to_tensor(ids)).numpy())
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-5)


# -- the router ----------------------------------------------------------------
def test_the_mlp_router_against_its_equations():
    rng = np.random.default_rng(0)
    T, d, R, E = 40, 24, 8, 4
    x, state = rng.standard_normal((T, d)), rng.standard_normal((T, R))
    wd, w1, w2 = (rng.standard_normal(s) * 0.5
                  for s in ((d, R), (R, R), (R, R)))
    w3, gamma = rng.standard_normal((R, E)), np.array([0.7])
    weights, experts, new = ops.moe_route_mlp(*(pt.to_tensor(
        a.astype(np.float32)) for a in (x, state, wd, gamma, w1, w2, w3)))
    gelu = lambda v: 0.5 * v * (1 + np.vectorize(math.erf)(  # noqa: E731
        v / math.sqrt(2)))
    r = x @ wd + 0.7 * state
    logits = gelu(gelu(r @ w1) @ w2) @ w3
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(new.numpy(), r, atol=1e-5)
    assert (experts.numpy()[:, 0] == p.argmax(-1)).all()
    np.testing.assert_allclose(weights.numpy()[:, 0], p.max(-1), atol=1e-6)
    assert weights.shape == [T, 1] and experts.numpy().dtype == np.int32


@pytest.mark.parametrize("chosen,counts", [
    (2, [0, 0, 40, 0]),     # every token on one held expert, three empty
    (5, [0, 0, 0, 0]),      # every token on an expert held elsewhere
])
def test_one_choice_a_token_at_the_extremes_drops_nothing(chosen, counts):
    rng = np.random.default_rng(3)
    T, d, w = 40, 16, 8
    x = rng.standard_normal((T, d)).astype(np.float32)
    gu = rng.standard_normal((4, d, 2 * w)).astype(np.float32) * 0.3
    down = rng.standard_normal((4, w, d)).astype(np.float32) * 0.3
    weights = rng.uniform(0.2, 0.9, (T, 1)).astype(np.float32)
    experts = np.full((T, 1), chosen, np.int32)
    y, got = ops.moe_experts(*(pt.to_tensor(a) for a in (
        x, weights, experts, gu, down)), 0)
    assert got.numpy().tolist() == counts
    if chosen < 4:
        a = x @ gu[chosen]
        act = a[:, :w] / (1 + np.exp(-a[:, :w])) * a[:, w:]
        np.testing.assert_allclose(y.numpy(), weights * (act @ down[chosen]),
                                   atol=1e-5)
    else:
        assert not y.numpy().any()


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "pallas"])
def test_a_held_expert_without_a_token_between_two_with(interpret):
    """Top-1 with expert 1 of the held four empty: the kernels' layout
    gives it a tile of padding, its weights a zero gradient."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.moe_ops import moe_experts
    rng = np.random.default_rng(4)
    T, d, w = 300, 16, 8
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    gu = jnp.asarray(rng.standard_normal((4, d, 2 * w)) * 0.3, jnp.float32)
    down = jnp.asarray(rng.standard_normal((4, w, d)) * 0.3, jnp.float32)
    weights = jnp.asarray(rng.uniform(0.2, 0.9, (T, 1)), jnp.float32)
    experts = jnp.asarray(rng.choice([0, 2, 3, 6], (T, 1)), jnp.int32)

    def ours(x, gu, down, weights):
        return moe_experts.raw_fn(x, weights, experts, gu, down, 0,
                                  interpret=interpret)[0]

    def plain(x, gu, down, weights):
        out = 0
        for e in range(4):
            a = x @ gu[e]
            y = (jax.nn.silu(a[:, :w]) * a[:, w:]) @ down[e]
            out = out + jnp.where(experts == e, weights, 0) * y
        return out

    np.testing.assert_allclose(ours(x, gu, down, weights),
                               plain(x, gu, down, weights), atol=2e-5)
    cot = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * cot), (0, 1, 2, 3))(
        x, gu, down, weights)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * cot), (0, 1, 2, 3))(
        x, gu, down, weights)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=2e-4)
    assert not np.asarray(got[1][1]).any()


def test_gradients_are_the_same_with_and_without_recompute():
    """The router's state runs beside x through per-block `recompute`:
    every leaf's gradient, the depth averaging's scale among them."""
    ids, labels = _batch()
    grads = []
    for recompute in (False, True):
        pt.seed(0)
        model = ZayaForCausalLM(zaya_tiny(recompute=recompute))
        model.train()
        loss = GPTPretrainingCriterion()(model(pt.to_tensor(ids)),
                                         pt.to_tensor(labels))
        loss.backward()
        grads.append({n: p.grad.numpy()
                      for n, p in model.named_parameters()})
    for name, g in grads[0].items():
        np.testing.assert_allclose(grads[1][name], g, atol=1e-6,
                                   err_msg=name)
    # the state of layer 0 reaches layer 1's choice weights, and there is
    # none before layer 0
    assert np.abs(grads[0]["zaya.layers.1.moe.router.eda_scale"]).max() > 0
    assert not grads[0]["zaya.layers.0.moe.router.eda_scale"].any()


# -- the step ------------------------------------------------------------------
@pytest.mark.parametrize("recompute", [False, True])
def test_a_step_hands_counts_weight_and_choices_out_with_its_loss(
        recompute):
    pt.seed(0)
    model = ZayaForCausalLM(zaya_tiny(experts_held=(0, 2),
                                      recompute=recompute))
    model.train()
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels), (m.expert_counts, m.router_top_weight,
                                      m.expert_choice)

    step = TrainStep(model, AdamW(learning_rate=1e-3,
                                  parameters=model.parameters()),
                     loss_fn, has_aux=True)
    ids, labels = _batch()
    first = float(step(ids, labels).numpy())
    counts, top, choice = (np.asarray(a) for a in step.aux)
    assert counts.shape == (3, 2) and counts.dtype == np.int32
    assert top.shape == (3,) and (top >= 0.25).all() and (top <= 1).all()
    assert choice.shape == (3, 64) and choice.max() < 4
    # the counts are the choices that fell on the two held experts
    for layer in range(3):
        assert counts[layer].tolist() == [
            int((choice[layer] == e).sum()) for e in (0, 1)]
    for _ in range(3):
        last = float(step(ids, labels).numpy())
    assert last < first


def test_the_load_gauges_take_a_one_choice_routers_weight():
    from paddle_tpu.observability import metrics
    counts = np.array([[10, 30], [25, 15]], np.int32)
    got = nn.observe_expert_load(counts, 80, np.array([0.5, 0.7]))
    assert got["moe.assignments_held"] == 0.5
    assert got["moe.load_max_over_mean"] == (1.5 + 1.25) / 2
    assert got["moe.top1_weight_mean"] == pytest.approx(0.6)
    assert "moe.top1_weight_mean" not in nn.observe_expert_load(counts, 80)
    metrics.enable()
    try:
        nn.observe_expert_load(counts, 80, np.array([0.5, 0.7]))
        text = metrics.registry().to_prometheus()
        assert "paddle_tpu_moe_top1_weight_mean 0.6" in text
        assert "paddle_tpu_moe_assignments_held 0.5" in text
    finally:
        metrics.disable()
