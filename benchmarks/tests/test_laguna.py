"""The Laguna configuration and cell at a size a CPU test can hold: the
program against the plain reference on seeded weights (logits, loss,
every gradient leaf, two AdamW steps), the vocabulary's shares, the one
command end to end, the comparison's verdicts, the configuration's file
against the published row, and the counted costs against hand counts.
The readers against a trace recorded on the chip are in
`test_laguna_trace.py`."""
import json

import numpy as np
import pytest

from harness.spec import REPO, Spec

CONFIG, CELL = "laguna-xs2-l5-e64", "laguna-xs2-l5-e64.train-8k"
# the tiny size's own: 64 tokens a step and weights of 0.15 make a bf16
# step's worst leaf read 0.02 to 0.06 and the fp8 control's 0.4 and more
TINY_LIMITS = {"grad_norm_worst_leaf": 0.15, "change_norm_median_leaf": 0.02}
# smaller than `tiny.TINY_MODEL` leaves it: few narrow experts, a window
# shorter than the 32-token rows, a share that starts at expert 4
SMALL = dict(moe_intermediate_size=32, shared_expert_intermediate_size=32,
             num_experts=8, expert_first=4, num_key_value_heads=2,
             num_attention_heads_per_layer=[4, 8, 8, 8, 4],
             sliding_window=8)


@pytest.fixture
def tiny_spec(tmp_path):
    import tiny
    return Spec(tiny.make_tiny_repo(str(tmp_path / "r"), limits=TINY_LIMITS))


@pytest.fixture
def small(tiny_spec):
    """(cfg, reference module, driver module, mix) at the SMALL size."""
    cfg = tiny_spec.data("configs", CONFIG)
    cfg.update(SMALL)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    return (cfg, tiny_spec.module("reference", CONFIG),
            tiny_spec.module("drivers", "laguna_train_window"),
            tiny_spec.data("traffic", "pretrain-8k"))


def _ids(cfg, seed=0, rows=2, seq=32):
    toks = np.random.default_rng(seed).integers(
        0, cfg["real_vocab_size"], (rows, seq + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


# -- the program against the reference ------------------------------------
def test_logits_loss_and_every_gradient_leaf_match_the_reference(small):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from harness import laguna_program, laguna_reference as lr
    from paddle_tpu.models import GPTPretrainingCriterion
    cfg, ref, _tw, _mix = small
    ids, labels = _ids(cfg)
    model = laguna_program.build_model(cfg, 5, ref)
    model.eval()
    plain = ref.Model(cfg, 5)
    logits = model(pt.to_tensor(ids))
    np.testing.assert_allclose(logits.numpy(), plain.logits(ids),
                               atol=3e-5)
    # the held experts' counts are those of the reference's choices
    x, want = plain.params[0][jnp.asarray(ids)], []
    for i in range(cfg["num_hidden_layers"]):
        x, counts = plain._blocks[plain.kind(i)](
            plain.layer(i), x, plain.rope(i, ids.shape[1]))
        if counts.size:
            want.append(np.asarray(counts))
    assert (model.expert_counts.numpy() == np.stack(want)).all()

    loss = GPTPretrainingCriterion()(logits, pt.to_tensor(labels))
    loss.backward()

    def ref_loss(params):
        x = params[0][jnp.asarray(ids)]
        for i in range(cfg["num_hidden_layers"]):
            lo, hi = plain.bounds[i]
            x, _counts = lr.block(
                params[lo:hi], x, plain.rope(i, ids.shape[1]), cfg=cfg,
                kind=plain.kind(i), rnd=lr.exact)
        return lr.head_loss(x, params[-2], params[-1], jnp.asarray(labels),
                            eps=cfg["rms_norm_eps"], rnd=lr.exact) / ids.size

    want_loss, want = jax.value_and_grad(ref_loss)(plain.params)
    np.testing.assert_allclose(float(loss.numpy()), float(want_loss),
                               rtol=2e-6)
    for (name, p), g in zip(model.named_parameters(), want):
        scale = float(jnp.abs(g).max()) + 1e-12
        np.testing.assert_allclose(p.grad.numpy() / scale, g / scale,
                                   atol=2e-4, err_msg=name)


@pytest.mark.parametrize("window", [None, 5, 8, 100])
def test_the_references_blocked_attention_is_the_whole_one(monkeypatch,
                                                           window):
    """Query blocks against the span of keys they can see (the reference
    at the cell's size) against every row against every key at once."""
    import jax
    import jax.numpy as jnp
    from harness import laguna_reference as lr
    rng = np.random.default_rng(0)
    s, h, heads, kv, d = 32, 24, 4, 2, 8
    u = jnp.asarray(rng.standard_normal((2, s, h)), jnp.float32)
    p = [jnp.asarray(rng.standard_normal(shape) * 0.3, jnp.float32)
         for shape in ((h, heads * d), (h, kv * d), (h, kv * d),
                       (heads * d, h))]
    rope = lr.rope_table(s, d, {"rope_type": "default", "rope_theta": 100.0})
    kw = dict(heads=heads, kv_heads=kv, d=d, window=window, rope=rope,
              rnd=lr.exact)
    whole = lr.attention(p, u, **kw)            # one block: Q_BLOCK >= s
    monkeypatch.setattr(lr, "Q_BLOCK", 8)
    blocked, vjp = jax.vjp(lambda p, u: lr.attention(p, u, **kw), p, u)
    np.testing.assert_allclose(blocked, whole, atol=2e-6)
    vjp(jnp.ones_like(blocked))                 # and it transposes
    if window == 5:     # against the band written out
        q = lr.rotate((u @ p[0]).reshape(2, s, heads, d), *rope)
        k = lr.rotate((u @ p[1]).reshape(2, s, kv, d), *rope)
        v = (u @ p[2]).reshape(2, s, kv, d)
        i = np.arange(s)
        band = (i[:, None] >= i[None]) & (i[:, None] - i[None] < 5)
        att = jnp.einsum("rqnd,rknd->rnqk", q, jnp.repeat(k, 2, 2))
        att = jax.nn.softmax(jnp.where(band, att / np.sqrt(d), -jnp.inf), -1)
        o = jnp.einsum("rnqk,rknd->rqnd", att, jnp.repeat(v, 2, 2))
        np.testing.assert_allclose(o.reshape(2, s, -1) @ p[3], whole,
                                   atol=2e-6)


def test_two_adamw_steps_match_the_reference(small):
    """The timed path's own objects in float32 (no amp): `TrainStep` on
    the program against the reference's `Trainer`, every leaf's first
    gradient norm and change after two steps."""
    from drivers.train_window import leaf_gaps
    cfg, ref, tw, mix = small
    cfg = json.loads(json.dumps(cfg))
    cfg["training"]["amp"] = {"level": "O0", "dtype": "float32"}
    cfg["training"]["optimizer"]["moment_dtype"] = "float32"
    step = tw.build_step(cfg, 3, ref)
    prog = tw.first_steps(step, cfg, mix, 3, ref, 2)
    plain = tw.reference_steps(cfg, mix, 3, ref, 2)
    np.testing.assert_allclose(prog["losses"], plain["losses"], rtol=5e-6)
    assert max(leaf_gaps(prog["grad_norms"], plain["grad_norms"])) < 2e-3
    assert max(leaf_gaps(prog["change_norms"], plain["change_norms"])) < 2e-3
    counts = np.asarray(step.counts[0])
    assert counts.shape == (4, 8) and counts.dtype == np.int32


def test_four_vocabulary_slices_logits_are_the_uncut_heads(small):
    import paddle_tpu as pt
    from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
    cfg, _ref, _tw, _mix = small
    pt.seed(1)
    whole = LagunaForCausalLM(LagunaConfig.from_dict(cfg))
    hidden = pt.to_tensor(np.random.default_rng(2).standard_normal(
        (2, 8, cfg["hidden_size"])).astype(np.float32))
    want = whole.lm_logits(hidden).numpy()
    v = cfg["vocab_size"] // 4
    parts = []
    for j in range(4):
        part = LagunaForCausalLM(LagunaConfig.from_dict(
            dict(cfg, vocab_size=v)))
        part.lm_head.weight._data = whole.lm_head.weight._data[
            :, j * v:(j + 1) * v]
        parts.append(part.lm_logits(hidden).numpy())
    np.testing.assert_allclose(np.concatenate(parts, -1), want, atol=1e-6)


# -- the one command ---------------------------------------------------------
def test_the_cell_runs_end_to_end_and_is_correct(rehearse):
    line = rehearse(CELL, seconds=0.5, limits=TINY_LIMITS)
    assert line["correct"] is True and line["failed"] == 0, line
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert set(line["compared"]) == {"loss_step1", "loss_step2",
                                     "grad_norm_worst_leaf",
                                     "change_norm_median_leaf"}


def test_a_traced_rehearsal_reads_what_a_cpu_can_and_does_not_raise(
        rehearse):
    line = rehearse(CELL, seconds=0.5, trace=1, limits=TINY_LIMITS)
    assert line["correct"] is True
    # no TPU plane in a CPU trace: every device reader returns nothing;
    # the program's own counter is read all the same
    assert not {"moe_ffn_ms.train", "moe_route_ms.train",
                "gmm_roofline.train", "flash_window_roofline.train",
                "mfu_laguna.train"} & set(line["metrics"])
    assert line["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1.0


def test_the_fp8_control_reads_above_the_program(small):
    cfg, ref, tw, mix = small
    n = ref.CHECK_STEPS
    prog = tw.first_steps(tw.build_step(cfg, 1, ref), cfg, mix, 1, ref, n)
    exact = tw.reference_steps(cfg, mix, 1, ref, n)
    control = tw.reference_steps(cfg, mix, 1, ref, n, rnd=ref.fp8)
    sound = tw.compare(prog, exact, TINY_LIMITS)
    broken = tw.compare(control, exact, TINY_LIMITS)
    assert all(v["value"] <= v["limit"] for v in sound.values()), sound
    assert any(v["value"] > v["limit"] for v in broken.values()), broken


def test_a_part_of_the_batch_left_out_is_not_correct(rehearse, monkeypatch):
    from paddle_tpu.jit import TrainStep
    real = TrainStep.__call__

    def call(self, ids, labels):
        half = len(ids) // 2
        return real(self, ids[:half], labels[:half])

    monkeypatch.setattr(TrainStep, "__call__", call)
    line = rehearse(CELL, seconds=0.3, limits=TINY_LIMITS)
    assert line["correct"] is False


def test_the_window_dropped_is_not_correct(small, monkeypatch):
    """A program whose window layers see every earlier key: the first
    gradient's worst leaf tells."""
    from paddle_tpu.models import laguna
    cfg, ref, tw, mix = small
    real = laguna.LagunaAttention.__init__

    def init(self, config, index):
        real(self, config, index)
        self.window = None

    monkeypatch.setattr(laguna.LagunaAttention, "__init__", init)
    n = ref.CHECK_STEPS
    prog = tw.first_steps(tw.build_step(cfg, 2, ref), cfg, mix, 2, ref, n)
    exact = tw.reference_steps(cfg, mix, 2, ref, n)
    got = tw.compare(prog, exact, TINY_LIMITS)
    assert any(v["value"] > v["limit"] for v in got.values()), got


# -- the configuration's file --------------------------------------------------
PUBLISHED = {   # the row's `config`, but for the three per-layer lists
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
}


def test_the_file_holds_the_published_row_but_for_what_reduced_names():
    spec = Spec(REPO)
    cfg = spec.data("configs", CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] == (
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")
    cut = {"num_hidden_layers": 5, "num_experts": 64, "vocab_size": 25088}
    lists = {"layer_types", "mlp_layer_types",
             "num_attention_heads_per_layer"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) == set(cut) | lists
    for key, value in PUBLISHED.items():
        assert cfg[key] == cut.get(key, value), key
    for key, value in cut.items():
        assert cfg["published"][key] == PUBLISHED[key] != value
    # the lists: the leading dense full layer and one 3:1 period
    assert cfg["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    # the guide's floors: four layers after the dense one, 8 experts and
    # more, an eighth of the vocabulary and more
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 100352
    assert {"gating", "router", "qk_norm", "hidden_act", "rope",
            "initializer_range", "weights"} <= set(cfg["assumed"])
    assert "32 chips" in cfg["deployment"]


def test_the_program_reads_the_share_from_the_file():
    from paddle_tpu.models.laguna import LagunaConfig
    c = LagunaConfig.from_dict(Spec(REPO).data("configs", CONFIG))
    assert (c.num_experts, tuple(c.experts_held)) == (256, (0, 64))
    assert (c.vocab_size, c.num_hidden_layers) == (25088, 5)
    assert c.rope_parameters["full_attention"]["factor"] == 64


def test_the_cell_joins_the_shared_metrics_and_brings_its_own():
    doc = Spec(REPO).doc
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-8k", 1)
    assert len(doc["workloads"]) == 4
    mine = {m["name"] for m in doc["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine == {
        "step_device_ms.train", "device_idle.train", "head_loss_ms.train",
        "optimizer_unfused_ms.train", "recompute_ms.train",
        "host_step_ms.train", "step_lower_s.train", "step_compile_s.train",
        "moe_ffn_ms.train", "moe_route_ms.train", "gmm_roofline.train",
        "flash_window_roofline.train", "mfu_laguna.train",
        "moe_load_max_over_mean.train"}
    for m in doc["per_layer"]:
        if m["name"] in ("mfu.train", "flash_roofline.train"):
            assert CELL not in m["workloads"]


# -- counted costs -------------------------------------------------------------
def test_flops_against_the_issues_count():
    from harness import laguna_flops
    cfg = Spec(REPO).data("configs", CONFIG)
    assert laguna_flops.held_per_token(cfg) == 2.0
    parts = laguna_flops.parts_per_token(cfg, 8192)
    assert parts["routed_experts"] == 6.0 * 2 * 3 * 2048 * 512 * 4
    full = 12.0 * 48 * 128 * (8192 + 1) / 2 * 2
    assert parts["full_attention"] == full
    window = 12.0 * 64 * 128 * (512 * (8192 - 512) + 512 * 513 / 2) / 8192 * 3
    assert parts["window_attention"] == pytest.approx(window)
    attn_proj = 2 * (2 * 2048 * 6144 + 2 * 2048 * 1024) \
        + 3 * (2 * 2048 * 8192 + 2 * 2048 * 1024)
    other = 6.0 * (attn_proj + 3 * 2048 * 8192 + 2048 * 25088
                   + 4 * (2048 * 256 + 3 * 2048 * 512))
    assert parts["other"] == other
    total = laguna_flops.train_flops_per_token(cfg, 8192)
    assert total == sum(parts.values())
    assert [round(parts[k] / 1e9, 2) for k in (
        "routed_experts", "full_attention", "window_attention", "other")] \
        == [0.15, 0.60, 0.15, 1.73]


def test_gmm_costs_against_hand_counts():
    gmm = Spec(REPO).module("kernel_costs", "gmm")
    (a_ops, a_bytes), (b_ops, b_bytes) = gmm.variants("gmm", 100, 4, 8, 3)
    assert a_ops == 2 * 100 * 8 * 6 and b_ops == 2 * 100 * 3 * 8
    assert a_bytes == 2 * (100 * 8 + 100 * 6 + 4 * 8 * 6)
    assert b_bytes == 2 * (100 * 3 + 100 * 8 + 4 * 3 * 8)
    assert gmm.variants("dw", 100, 4, 8, 3) == [(a_ops, a_bytes),
                                                (b_ops, b_bytes)]
    path = "lagunaforcausallm/laguna/layers/2/moe/experts/"
    assert gmm.classify(path + "moe_gmm") == "gmm"
    assert gmm.classify(path + "moe_gmm_dw") == "dw"
    assert gmm.classify(path.rstrip("/")) is None
    with pytest.raises(KeyError):
        gmm.variants("fwd", 1, 1, 1, 1)


def test_flash_window_costs_against_hand_counts():
    fw = Spec(REPO).module("kernel_costs", "flash_window")
    assert fw.pairs(8, None) == 36 and fw.pairs(8, 100) == 36
    assert fw.pairs(8, 3) == 3 * 5 + 6          # 1 + 2 + 3 * 6
    ops, nbytes = fw.cost("fwd", 2, 8, 4, 2, 16, 3)
    product = 2 * 2 * 4 * 21 * 16
    assert ops == 2 * product
    q, kv, lse = 2 * 8 * 4 * 16 * 2, 2 * 8 * 2 * 16 * 2, 2 * 4 * 8 * 4
    assert nbytes == 2 * q + 2 * kv + lse
    assert fw.cost("bwd", 2, 8, 4, 2, 16, 3) == (
        4 * product, 4 * q + 4 * kv + 2 * lse)
    path = "lagunaforcausallm/laguna/layers/3/attn/"
    assert fw.classify(path + "flash_fwd") == ("fwd", 3)
    assert fw.classify("x/laguna/checkpoint/layers/0/attn/"
                       "flash_bwd_transpose") == ("bwd", 0)
    assert fw.classify(path + "q_proj") is None
    cfg = Spec(REPO).data("configs", CONFIG)
    assert fw.layer_shape(cfg, 0) == (48, 8, 128, None)
    assert fw.layer_shape(cfg, 2) == (64, 8, 128, 512)
