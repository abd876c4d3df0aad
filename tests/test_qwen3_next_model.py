"""`models/qwen3_next.py`, `nn.GatedDeltaNet`, the softmax router and
the shared expert's gate on the CPU at a tiny size: the mixers by index,
the Gated DeltaNet by its parts against a token-by-token recurrence, the
gated attention's gate and norms, the third router against its
equations, the share read from a configuration's dict, `recompute` over
two kinds of mixer, and what a `TrainStep(has_aux=True)` step hands out
and notes. The program against the plain reference is
`benchmarks/tests/test_qwen3next.py`."""
import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp, nn, ops
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import (GPTPretrainingCriterion, Qwen3NextConfig,
                               Qwen3NextForCausalLM, qwen3_next_tiny)
from paddle_tpu.models.qwen3_next import Qwen3NextAttention
from paddle_tpu.observability import perf
from paddle_tpu.optimizer import AdamW


def _batch(rows=2, seq=32, vocab=512, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, (rows, seq + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def _randomise(layer, seed=0, scale=0.3):
    """Every parameter drawn anew, so that none sits at the value that
    hides it (a norm weight of 0 or 1, a bias of -4.6)."""
    rng = np.random.default_rng(seed)
    for _n, p in layer.named_parameters():
        p._data = pt.to_tensor((rng.standard_normal(p.shape) * scale)
                               .astype(np.float32))._data


# -- the model's description -------------------------------------------------
def test_the_mixer_differs_by_index_three_linear_to_one_full():
    model = Qwen3NextForCausalLM(qwen3_next_tiny(num_hidden_layers=8))
    kinds = ["attn" if hasattr(layer, "attn") else "gdn"
             for layer in model.model.layers]
    assert kinds == ["gdn", "gdn", "gdn", "attn"] * 2
    for layer in model.model.layers:
        assert isinstance(layer.moe, nn.SparseExpertFFN)
        assert layer.moe.router.score == "softmax"
        assert layer.moe.shared_expert_gate is not None


def test_the_published_defaults_are_qwen3_next_80b():
    c = Qwen3NextConfig()
    assert (c.hidden_size, c.num_hidden_layers, c.vocab_size) == (
        2048, 48, 151936)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        16, 2, 256)
    assert (c.linear_num_key_heads, c.linear_num_value_heads,
            c.linear_key_head_dim, c.linear_conv_kernel_dim) == (
        16, 32, 128, 4)
    assert (c.num_experts, c.num_experts_per_tok, c.moe_intermediate_size,
            c.shared_expert_intermediate_size) == (512, 10, 512, 512)
    assert (c.partial_rotary_factor, c.rope_theta, c.rms_norm_eps) == (
        0.25, 1e7, 1e-6)
    assert sum(c.is_full(i) for i in range(48)) == 12 and c.is_full(3)
    assert c.experts_held == (0, 512)
    assert c.out_std == pytest.approx(0.02 / 96 ** 0.5)


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn"}), ("use_sliding_window", True),
    ("tie_word_embeddings", True), ("norm_topk_prob", False),
    ("decoder_sparse_step", 2), ("mlp_only_layers", (0,)),
    ("linear_value_head_dim", 64)])
def test_what_the_model_cannot_run_is_refused(key, value):
    with pytest.raises(NotImplementedError):
        Qwen3NextConfig(**{key: value})


def test_from_dict_reads_the_share_of_a_benchmark_configuration():
    d = dict(num_experts=64, expert_first=128, num_hidden_layers=4,
             vocab_size=18992, intermediate_size=5120, model_type="x",
             mlp_only_layers=[], rope_theta=10000000,
             published={"num_experts": 512, "num_hidden_layers": 48,
                        "vocab_size": 151936})
    c = Qwen3NextConfig.from_dict(d, recompute=True)
    assert c.experts_held == (128, 64) and c.num_experts == 512
    assert (c.num_hidden_layers, c.vocab_size) == (4, 18992)
    assert c.residual_depth == 48 and c.recompute
    assert c.out_std == pytest.approx(0.02 / 96 ** 0.5)
    # a whole model's dict: every expert held, its own depth
    whole = Qwen3NextConfig.from_dict({"num_experts": 16,
                                       "num_hidden_layers": 8})
    assert whole.experts_held == (0, 16) and whole.residual_depth is None


# -- the Gated DeltaNet ------------------------------------------------------
HK, HV, D, HIDDEN = 2, 4, 8, 32


def _gdn(seed=0):
    pt.seed(seed)
    layer = nn.GatedDeltaNet(HIDDEN, HK, HV, D, taps=4, std=0.3)
    _randomise(layer, seed)
    return layer


def _gdn_by_hand(layer, u):
    """The layer's equations token by token in numpy float64."""
    P = {n: np.asarray(p.numpy(), np.float64)
         for n, p in layer.named_parameters()}
    b, s, _ = u.shape
    G = HV // HK
    qkvz = (u @ P["in_proj_qkvz.weight"]).reshape(b, s, HK, (2 + 2 * G) * D)
    ba = (u @ P["in_proj_ba.weight"]).reshape(b, s, HK, 2 * G)
    q, k = qkvz[..., :D], qkvz[..., D:2 * D]
    v, z = qkvz[..., 2 * D:(2 + G) * D], qkvz[..., (2 + G) * D:]
    mixed = np.concatenate([x.reshape(b, s, -1) for x in (q, k, v)], -1)
    padded = np.pad(mixed, ((0, 0), (3, 0), (0, 0)))
    conv = sum(padded[:, j:j + s] * P["conv_weight"][:, j] for j in range(4))
    mixed = conv / (1 + np.exp(-conv))
    q = mixed[..., :HK * D].reshape(b, s, HK, D)
    k = mixed[..., HK * D:2 * HK * D].reshape(b, s, HK, D)
    v = mixed[..., 2 * HK * D:].reshape(b, s, HV, D)
    beta = 1 / (1 + np.exp(-ba[..., :G].reshape(b, s, HV)))
    g = -np.exp(P["A_log"]) * np.log1p(np.exp(
        ba[..., G:].reshape(b, s, HV) + P["dt_bias"]))
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(D)
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    q, k = np.repeat(q, G, axis=2), np.repeat(k, G, axis=2)
    o = np.zeros((b, s, HV, D))
    for r in range(b):
        for h in range(HV):
            S = np.zeros((D, D))
            for t in range(s):
                S = np.exp(g[r, t, h]) * S
                S = S + beta[r, t, h] * np.outer(
                    k[r, t, h], v[r, t, h] - S.T @ k[r, t, h])
                o[r, t, h] = S.T @ q[r, t, h]
    y = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) \
        * P["norm_weight"]
    z = z.reshape(b, s, HV, D)
    y = y * (z / (1 + np.exp(-z)))
    return y.reshape(b, s, HV * D) @ P["out_proj.weight"]


@pytest.mark.parametrize("seq", [12, 64, 70])
def test_the_gated_delta_net_is_its_equations_token_by_token(seq):
    """The layout by key head, the convolution, the gates, the unit
    norms, the rule (a row of 12 or 70 tokens is padded to whole chunks)
    and the gated norm, against numpy."""
    layer = _gdn()
    u = np.random.default_rng(1).standard_normal(
        (2, seq, HIDDEN)).astype(np.float32)
    got = layer(pt.to_tensor(u)).numpy()
    np.testing.assert_allclose(got, _gdn_by_hand(layer, u.astype(np.float64)),
                               rtol=2e-4, atol=2e-5)


def test_a_change_at_a_position_moves_no_output_before_it():
    pt.seed(0)
    model = Qwen3NextForCausalLM(qwen3_next_tiny())
    model.eval()
    ids, _ = _batch(rows=1, seq=24)
    other = ids.copy()
    other[0, 15] = (other[0, 15] + 7) % 512
    a = model(pt.to_tensor(ids)).numpy()
    b = model(pt.to_tensor(other)).numpy()
    np.testing.assert_allclose(a[:, :15], b[:, :15], atol=1e-5)
    assert np.abs(a[:, 15:] - b[:, 15:]).max() > 1e-3
    # the state carries it to every later position, not a window's worth
    assert np.abs(a[:, -1] - b[:, -1]).max() > 1e-4


# -- the gated attention ------------------------------------------------------
def _attention(seed=0):
    pt.seed(seed)
    layer = Qwen3NextAttention(qwen3_next_tiny())
    _randomise(layer, seed, 0.2)
    return layer


def test_the_gated_attention_is_its_equations():
    """q_proj's layout (a head's query and its gate side by side), the
    zero-centred norms a head, the partial rotary, 4 heads on 2, the
    sigmoid gate on the output: against numpy."""
    from paddle_tpu.nn import rope_tables
    layer = _attention()
    P = {n: np.asarray(p.numpy(), np.float64)
         for n, p in layer.named_parameters()}
    b, s, H, Hk, d = 2, 10, 4, 2, 16
    u = np.random.default_rng(1).standard_normal((b, s, 64))
    cos, sin = rope_tables(s, d, partial_rotary_factor=0.5)
    got = layer(pt.to_tensor(u.astype(np.float32)), cos, sin).numpy()

    def normed(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * (1 + w)

    def turned(x):
        c, sn = (np.asarray(t, np.float64)[None, :, None] for t in (cos, sin))
        xr, rest = x[..., :8], x[..., 8:]
        half = np.concatenate([-xr[..., 4:], xr[..., :4]], -1)
        return np.concatenate([xr * c + half * sn, rest], -1)

    qg = (u @ P["q_proj.weight"]).reshape(b, s, H, 2 * d)
    q = turned(normed(qg[..., :d], P["q_norm.weight"]))
    gate = qg[..., d:]
    k = turned(normed((u @ P["k_proj.weight"]).reshape(b, s, Hk, d),
                      P["k_norm.weight"]))
    v = (u @ P["v_proj.weight"]).reshape(b, s, Hk, d)
    k, v = np.repeat(k, 2, axis=2), np.repeat(v, 2, axis=2)
    att = np.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    att = np.where(np.tril(np.ones((s, s), bool)), att, -np.inf)
    att = np.exp(att - att.max(-1, keepdims=True))
    att /= att.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", att, v) / (1 + np.exp(-gate))
    want = o.reshape(b, s, H * d) @ P["o_proj.weight"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_q_and_k_norms_are_zero_centred():
    """At w = 0 the norm is the plain RMSNorm with weight 1, and w adds
    to that one."""
    x = np.random.default_rng(0).standard_normal((3, 5, 16)).astype(
        np.float32)
    layer = nn.ZeroCenteredRMSNorm(16, 1e-6)
    assert not layer.weight.numpy().any()
    unit = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(layer(pt.to_tensor(x)).numpy(), unit,
                               rtol=1e-5)
    w = np.linspace(-0.5, 0.5, 16).astype(np.float32)
    layer.weight._data = pt.to_tensor(w)._data
    np.testing.assert_allclose(layer(pt.to_tensor(x)).numpy(),
                               unit * (1 + w), rtol=1e-5)


def test_the_composite_and_the_flash_entry_agree():
    ids, _ = _batch(rows=1, seq=16)
    outs = []
    for flash in (False, True):
        pt.seed(0)
        model = Qwen3NextForCausalLM(qwen3_next_tiny(
            use_flash_attention=flash))
        model.eval()
        outs.append(model(pt.to_tensor(ids)).numpy())
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-5)


# -- the third router and the shared expert's gate ------------------------------
@pytest.mark.parametrize("top_k", [1, 4, 10])
def test_the_softmax_router_is_a_softmax_over_the_chosen_logits(top_k):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((40, 32)).astype(np.float32)
    w = rng.standard_normal((32, 24)).astype(np.float32)
    weights, experts = (t.numpy() for t in ops.moe_route(
        pt.to_tensor(x), pt.to_tensor(w), top_k, 1.0, "softmax"))
    logits = x.astype(np.float64) @ w
    want = np.argsort(-logits, axis=-1)[:, :top_k]
    assert (experts == want).all()
    chosen = np.take_along_axis(logits, want, axis=-1)
    soft = np.exp(chosen - chosen.max(-1, keepdims=True))
    np.testing.assert_allclose(weights, soft / soft.sum(-1, keepdims=True),
                               rtol=2e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)


def test_the_sigmoid_router_is_what_it_was():
    """`score` defaults to the Laguna router: the same jaxpr with and
    without the argument, and another than the softmax's."""
    x, w = np.zeros((4, 8), np.float32), np.zeros((8, 6), np.float32)
    from paddle_tpu.ops.registry import OPS
    fn = OPS["moe_route"].fn
    plain = str(jax.make_jaxpr(lambda x, w: fn(x, w, 2, 2.5))(x, w))
    named = str(jax.make_jaxpr(
        lambda x, w: fn(x, w, 2, 2.5, "sigmoid"))(x, w))
    soft = str(jax.make_jaxpr(
        lambda x, w: fn(x, w, 2, 2.5, "softmax"))(x, w))
    assert plain == named and "logistic" in plain
    assert soft != plain and "logistic" not in soft
    with pytest.raises(KeyError):
        fn(x, w, 2, 2.5, "tanh")


def test_the_shared_experts_gate_is_a_sigmoid_a_token():
    pt.seed(0)
    kw = dict(num_experts=4, top_k=2, shared_width=16, routed_scale=1.0,
              router_score="softmax")
    gated = nn.SparseExpertFFN(32, 16, shared_gate=True, **kw)
    pt.seed(0)
    plain = nn.SparseExpertFFN(32, 16, **kw)
    assert plain.shared_expert_gate is None
    for (_n, p), (_m, q) in zip(plain.named_parameters(),
                                gated.named_parameters()):
        q._data = p._data
    x = pt.to_tensor(np.random.default_rng(1).standard_normal(
        (2, 6, 32)).astype(np.float32))
    y_plain, c_plain = plain(x)
    y_gated, c_gated = gated(x)
    shared = plain.shared_expert(x).numpy()
    gate = 1 / (1 + np.exp(-(x.numpy() @ gated.shared_expert_gate.weight
                             .numpy())))
    assert gate.shape == (2, 6, 1)
    np.testing.assert_allclose(y_gated.numpy(),
                               y_plain.numpy() - shared + gate * shared,
                               atol=1e-5)
    assert (c_plain.numpy() == c_gated.numpy()).all()


# -- recompute and the step ---------------------------------------------------
def test_gradients_are_the_same_with_and_without_recompute():
    """Per-block `recompute` over two kinds of mixer: every leaf's
    gradient, the linear layers' (their state pass runs again) and the
    full layer's."""
    ids, labels = _batch(seq=70)
    grads = []
    for recompute in (False, True):
        pt.seed(0)
        model = Qwen3NextForCausalLM(qwen3_next_tiny(recompute=recompute))
        _randomise(model, 3, 0.1)
        model.train()
        loss = GPTPretrainingCriterion()(model(pt.to_tensor(ids)),
                                         pt.to_tensor(labels))
        loss.backward()
        grads.append({n: p.grad.numpy()
                      for n, p in model.named_parameters()})
    assert len(grads[0]) == 3 * 16 + 15 + 3
    for name, g in grads[0].items():
        assert np.abs(g).max() > 0, name
        np.testing.assert_allclose(grads[1][name], g, atol=1e-6,
                                   rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("recompute", [False, True])
def test_a_step_hands_counts_out_with_its_loss_and_notes_its_paths(
        recompute):
    pt.seed(0)
    model = Qwen3NextForCausalLM(qwen3_next_tiny(experts_held=(4, 8),
                                                 recompute=recompute))
    model.train()
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels), m.expert_counts

    step = TrainStep(model, AdamW(learning_rate=1e-3,
                                  parameters=model.parameters(),
                                  moment_dtype="bfloat16"),
                     loss_fn, has_aux=True)
    ids, labels = _batch(seq=64)
    first = float(step(ids, labels).numpy())
    counts = np.asarray(step.aux)
    assert counts.shape == (4, 8) and counts.dtype == np.int32
    # 128 tokens x 4 choices over 16 experts, half of them held
    assert (counts.sum(1) > 128).all() and (counts.sum(1) < 384).all()
    for _ in range(3):
        last = float(step(ids, labels).numpy())
    assert last < first
    record = perf.compile_record("train_step")
    assert record["gdn"] == ("heads 4 on 2, state 8 x 8, chunk 64, conv 4 "
                             "taps, operands: xla, q k at 2 heads, chunk "
                             "preparation: xla, state pass: lax.scan")
    assert "experts 8 held of 16, top 4" in record["moe"]
    assert record["moe"].endswith("softmax scores")
    assert record["rope"].startswith("composite")
    if recompute:
        assert record["flash_kept"] == ("o and lse kept across recompute "
                                        "in 0 of 4 recomputed layers")


@pytest.mark.parametrize("lazy", [True, False], ids=["lazy", "drawn"])
def test_a_lazy_guard_builds_placeholders_and_draws_nothing(lazy):
    """Under `pt.LazyGuard` every parameter is zeros of its shape and
    type and the generator is not asked for a key (a harness hands the
    weights in next); outside it, before and after, they are drawn."""
    import contextlib
    from paddle_tpu.core.generator import default_generator
    pt.seed(3)
    before = default_generator().get_state()
    with pt.LazyGuard() if lazy else contextlib.nullcontext():
        layer = nn.SparseExpertFFN(16, 8, 4, 2, held=(0, 2),
                                   router_score="softmax", shared_width=8,
                                   shared_gate=True)
    drawn = [bool(np.asarray(p.numpy()).any()) for p in layer.parameters()]
    moved = default_generator().get_state() != before
    assert (not any(drawn) and not moved) if lazy else (all(drawn) and moved)
    assert not pt.LazyGuard.on
    assert [tuple(p.shape) for p in layer.parameters()] == [
        tuple(p.shape) for p in nn.SparseExpertFFN(
            16, 8, 4, 2, held=(0, 2), router_score="softmax",
            shared_width=8, shared_gate=True).parameters()]


def test_a_stacked_leaf_is_drawn_as_rows_and_reads_the_same():
    """`Normal` draws more than two dimensions as [rows, last] (a third
    of the compile on a TPU): the numbers are those of the plain draw."""
    import jax.numpy as jnp
    from paddle_tpu.core.generator import default_generator, next_key
    from paddle_tpu.nn.initializer import Normal
    pt.seed(5)
    state = default_generator().get_state()
    got = Normal(mean=0.5, std=0.1)((3, 4, 8), jnp.float32)
    default_generator().set_state(state)
    want = jax.random.normal(next_key(), (3, 4, 8), jnp.float32) * 0.1 + 0.5
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
