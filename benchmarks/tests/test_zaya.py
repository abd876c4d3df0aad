"""The ZAYA1 configuration and cell at a size a CPU test can hold: the
program against the plain reference on seeded weights (logits, loss,
every gradient leaf, two AdamW steps), the expert and the vocabulary
shares against the uncut sublayer and head, the one command end to end,
the comparison's verdicts (the fp8 control and a program without the
value shift, a convolution or the depth averaging), the configuration's
file against the published row, and the counted costs against hand
counts. The readers against a trace recorded on the chip are in
`test_zaya_trace.py`."""
import json

import numpy as np
import pytest

from harness.spec import REPO, Spec

CONFIG, CELL = "zaya1-8b-l5-e8", "zaya1-8b-l5-e8.train-32k"
# the tiny size's own: 64 tokens a step and weights of 0.15 make a bf16
# step's worst leaf read 0.02 to 0.07 and the controls' 0.2 and more
TINY_LIMITS = {"grad_norm_worst_leaf": 0.15, "change_norm_median_leaf": 0.02}
# smaller than `tiny.TINY_MODEL` leaves it: 4 latent heads on 2, two of
# four narrow experts held from expert 2 on, a router whose logits spread
# by 3 and draws under which every part is felt
SMALL = dict(num_attention_heads=4, num_key_value_heads=2,
             moe_intermediate_size=32, router_hidden_size=16,
             num_experts=2, expert_first=2, num_hidden_layers=3,
             layer_types=["hybrid"] * 3,
             seeded_draws={"embedding": 0.3, "residual_output": 0.1,
                           "final_norm": 1.0,
                           "router_down": 1.0, "router_fc1": 1.0,
                           "router_fc2": 1.0, "router_out": 3.0})


@pytest.fixture
def tiny_spec(tmp_path):
    import tiny
    return Spec(tiny.make_tiny_repo(str(tmp_path / "r"), limits=TINY_LIMITS))


@pytest.fixture
def small(tiny_spec):
    """(cfg, reference module, driver module, mix) at the SMALL size."""
    cfg = tiny_spec.data("configs", CONFIG)
    cfg.update(SMALL)
    cfg["published"] = dict(cfg["published"], num_experts=4)
    return (cfg, tiny_spec.module("reference", CONFIG),
            tiny_spec.module("drivers", "zaya_train_window"),
            tiny_spec.data("traffic", "pretrain-32k"))


def _ids(cfg, seed=0, rows=2, seq=32):
    toks = np.random.default_rng(seed).integers(
        0, cfg["real_vocab_size"], (rows, seq + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def _ref_loss(zr, plain, cfg, ids, labels):
    import jax.numpy as jnp

    def loss(params):
        x = params[0][jnp.asarray(ids)]
        r = plain.state0(x)
        for i in range(cfg["num_hidden_layers"]):
            lo = 1 + i * zr.LAYER_LEAVES
            (x, r), _aux = zr.block(
                params[lo:lo + zr.LAYER_LEAVES], x, r,
                plain.rope(ids.shape[1]), cfg=cfg, rnd=zr.exact)
        return zr.head_loss(x, params[-1], params[0], jnp.asarray(labels),
                            eps=cfg["rms_norm_eps"], rnd=zr.exact) / ids.size
    return loss


# -- the program against the reference ------------------------------------
def test_logits_loss_and_every_gradient_leaf_match_the_reference(small):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from harness import zaya_program, zaya_reference as zr
    from paddle_tpu.models import GPTPretrainingCriterion
    cfg, ref, _tw, _mix = small
    ids, labels = _ids(cfg)
    model = zaya_program.build_model(cfg, 5, ref)
    model.eval()
    plain = ref.Model(cfg, 5)
    logits = model(pt.to_tensor(ids))
    np.testing.assert_allclose(logits.numpy(), plain.logits(ids),
                               atol=5e-5)
    # the counts, the mean chosen probability and each token's choice
    # are those of the reference's router
    x = plain.params[0][jnp.asarray(ids)]
    r, want = plain.state0(x), []
    for i in range(cfg["num_hidden_layers"]):
        (x, r), aux = plain._block(plain.layer(i), x, r,
                                   plain.rope(ids.shape[1]))
        want.append(aux)
    counts, tops, choices = (np.stack([np.asarray(a[j]) for a in want])
                             for j in range(3))
    assert (model.expert_counts.numpy() == counts).all()
    assert (model.expert_choice.numpy()
            == choices.reshape(len(want), -1)).all()
    np.testing.assert_allclose(model.router_top_weight.numpy(),
                               tops / ids.size, rtol=1e-5)
    assert 0 < counts.sum() < ids.size * len(want)  # some held, some not

    loss = GPTPretrainingCriterion()(logits, pt.to_tensor(labels))
    loss.backward()
    want_loss, want = jax.value_and_grad(
        _ref_loss(zr, plain, cfg, ids, labels))(plain.params)
    np.testing.assert_allclose(float(loss.numpy()), float(want_loss),
                               rtol=2e-6)
    for (name, p), g in zip(model.named_parameters(), want):
        scale = float(jnp.abs(g).max()) + 1e-12
        # 5e-4: the paired router's sums cancel (gelu(p) - gelu(-p)), and
        # float32 products of two orders keep three digits of what is left
        np.testing.assert_allclose(p.grad.numpy() / scale, g / scale,
                                   atol=5e-4, err_msg=name)


def test_the_references_blocked_attention_is_the_whole_one(monkeypatch):
    import jax
    import jax.numpy as jnp
    from harness import zaya_reference as zr
    rng = np.random.default_rng(0)
    s, H, Hk, d = 32, 4, 2, 8
    q = jnp.asarray(rng.standard_normal((2, s, H, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, s, Hk, d)), jnp.float32)
            for _ in range(2))
    whole = zr.causal_attention(q, k, v, zr.exact)   # one block
    monkeypatch.setattr(zr, "Q_BLOCK", 8)
    blocked, vjp = jax.vjp(
        lambda q, k, v: zr.causal_attention(q, k, v, zr.exact), q, k, v)
    np.testing.assert_allclose(blocked, whole, atol=2e-6)
    vjp(jnp.ones_like(blocked))                      # and it transposes
    i = np.arange(s)
    att = jnp.einsum("rqnd,rknd->rnqk", q, jnp.repeat(k, 2, 2)) / np.sqrt(d)
    att = jax.nn.softmax(jnp.where(i[:, None] >= i[None], att, -jnp.inf), -1)
    o = jnp.einsum("rnqk,rknd->rqnd", att, jnp.repeat(v, 2, 2))
    np.testing.assert_allclose(o.reshape(2, s, -1), whole, atol=2e-6)


def test_the_routers_seeded_matrices_are_orthonormal_and_paired(small):
    """The draws that keep a random router's load even (the file's
    `assumed.weights`): W_down is Q of the leaf's normal draw times its
    gain; the MLP's matrices are an orthonormal block laid out [B, -B]
    along the hidden units' axes, so that the MLP is its own linear
    part; the same array again from the same seed, another from
    another."""
    from harness import zaya_reference as zr
    cfg, ref, _tw, _mix = small
    specs = ref.param_specs(cfg)
    first, again, other = (dict(zip((n for n, _s, _i in specs),
                                    zr.make(seed, specs, "float32")))
                           for seed in (3, 3, 4))
    gains, half = cfg["seeded_draws"], cfg["router_hidden_size"] // 2
    got = {}
    for leaf, gain in (("down_proj", "router_down"), ("fc1", "router_fc1"),
                       ("fc2", "router_fc2"), ("fc3", "router_out")):
        name = f"zaya.layers.1.moe.router.{leaf}"
        got[leaf] = np.asarray(first[name], np.float64) / gains[gain]
        assert (np.asarray(again[name]) == np.asarray(first[name])).all()
        assert not np.allclose(other[name], first[name])
    w = got["down_proj"]
    np.testing.assert_allclose(w.T @ w, np.eye(w.shape[1]), atol=1e-5)
    f1, f2, f3 = got["fc1"], got["fc2"], got["fc3"]
    np.testing.assert_array_equal(f1[:, half:], -f1[:, :half])
    np.testing.assert_array_equal(f2[:, half:], -f2[:, :half])
    np.testing.assert_array_equal(f2[half:], -f2[:half])
    np.testing.assert_array_equal(f3[half:], -f3[:half])
    for block in (f1[:, :half], f2[:half, :half], f3[:half]):
        np.testing.assert_allclose(block.T @ block,
                                   np.eye(block.shape[1]), atol=1e-5)
    # gelu(p) - gelu(-p) = p: the logits are r times one matrix
    r = np.random.default_rng(0).standard_normal((7, 2 * half)) * 3.0
    gelu = lambda x: np.asarray(zr.gelu(x))          # noqa: E731
    logits = gelu(gelu(r @ f1) @ f2) @ f3
    np.testing.assert_allclose(
        logits, r @ f1[:, :half] @ f2[:half, :half] @ f3[:half],
        atol=1e-5)
    norms = ref.change_norms(zr.make(3, specs, "float32"), specs, 3)
    # to a rounding: one program for all leaves against one a leaf
    assert max(norms) < 1e-5 and len(norms) == len(specs)


def test_two_adamw_steps_match_the_reference(small):
    """The timed path's own objects in float32 (no amp): `TrainStep` on
    the program against the reference's `Trainer`, every leaf's first
    gradient norm and change after two steps."""
    from drivers.train_window import leaf_gaps
    cfg, ref, tw, mix = small
    cfg = json.loads(json.dumps(cfg))
    cfg["training"]["amp"] = {"level": "O0", "dtype": "float32"}
    cfg["training"]["optimizer"]["moment_dtype"] = "float32"
    # the cell's 1e-6 moves a weight of 1.0 by eight float32 steps: the two
    # sides then differ by roundings of the change, not by arithmetic
    cfg["training"]["optimizer"]["learning_rate"] = 1e-4
    step = tw.build_step(cfg, 3, ref)
    prog = tw.first_steps(step, cfg, mix, 3, ref, 2)
    plain = tw.reference_steps(cfg, mix, 3, ref, 2)
    np.testing.assert_allclose(prog["losses"], plain["losses"], rtol=5e-6)
    assert max(leaf_gaps(prog["grad_norms"], plain["grad_norms"])) < 2e-3
    assert max(leaf_gaps(prog["change_norms"], plain["change_norms"])) < 2e-3
    counts, top, choice = (np.asarray(a) for a in step.counts[0])
    assert counts.shape == (3, 2) and counts.dtype == np.int32
    assert (counts == plain["held_counts"]).all()
    assert (choice == plain["choices"]).all()
    np.testing.assert_allclose(top, plain["top_weight_mean"], rtol=1e-5)


# -- the share -----------------------------------------------------------------
def test_two_expert_shares_add_up_to_the_uncut_sublayer(small):
    """4 experts in two shares of 2: the routed parts the two chips
    compute, with what both compute alike (the router, its state, the
    residual's own vectors) counted once, are the uncut reference's
    sublayer output."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from harness import zaya_reference as zr
    from paddle_tpu import nn
    cfg, ref, _tw, _mix = small
    h, w, R = cfg["hidden_size"], cfg["moe_intermediate_size"], 16
    rng = np.random.default_rng(1)
    u = rng.standard_normal((2, 24, h)).astype(np.float32)
    state = rng.standard_normal((2, 24, R)).astype(np.float32) * 0.3
    gu = rng.standard_normal((4, h, 2 * w)).astype(np.float32) * 0.2
    down = rng.standard_normal((4, w, h)).astype(np.float32) * 0.2
    router = [rng.standard_normal(s).astype(np.float32) * g for s, g in (
        ((h, R), 0.2), ((1,), 1.0), ((R, R), 0.8), ((R, R), 0.8),
        ((R, 4), 2.0))]
    uncut, r_want, counts_want, _top, choice = zr.sparse_ffn(
        [jnp.asarray(a) for a in [gu, down] + router], jnp.asarray(u),
        jnp.asarray(state), first=0, rnd=zr.exact)
    both = {int(e) // 2 for e in np.asarray(choice).ravel()}
    assert both == {0, 1}               # each share has tokens
    parts, counts = [], []
    for first in (0, 2):
        pt.seed(0)
        layer = nn.SparseExpertFFN(h, w, num_experts=4, top_k=1,
                                   held=(first, 2), shared_width=0,
                                   router_mlp=(R, (1.0, 1.0, 1.0)))
        layer.gate_up_proj._data = jnp.asarray(gu[first:first + 2])
        layer.down_proj._data = jnp.asarray(down[first:first + 2])
        for p, a in zip((layer.router.down_proj, layer.router.eda_scale,
                         layer.router.fc1, layer.router.fc2,
                         layer.router.fc3), router):
            p._data = jnp.asarray(a)
        y, c, r, _weights, experts = layer(pt.to_tensor(u),
                                           pt.to_tensor(state))
        parts.append(y.numpy())
        counts += c.numpy().tolist()
        # alike on both chips: the state handed on and the choices
        np.testing.assert_allclose(r.numpy(), r_want, atol=1e-5)
        assert (experts.numpy().ravel() == np.asarray(choice).ravel()).all()
    np.testing.assert_allclose(parts[0] + parts[1], uncut, atol=1e-5)
    assert counts == np.asarray(counts_want).tolist()
    assert sum(counts) == 48                    # none dropped
    # a token's routed output lives on one chip alone
    assert not (np.abs(parts[0]).sum(-1) * np.abs(parts[1]).sum(-1)).any()


def test_two_vocabulary_halves_logits_are_the_uncut_heads(small):
    import paddle_tpu as pt
    from paddle_tpu.models.zaya import ZayaConfig, ZayaForCausalLM
    cfg, _ref, _tw, _mix = small
    pt.seed(1)
    whole = ZayaForCausalLM(ZayaConfig.from_dict(cfg))
    hidden = pt.to_tensor(np.random.default_rng(2).standard_normal(
        (2, 8, cfg["hidden_size"])).astype(np.float32))
    want = whole.lm_logits(hidden).numpy()
    v = cfg["vocab_size"] // 2
    parts = []
    for j in range(2):
        part = ZayaForCausalLM(ZayaConfig.from_dict(dict(cfg, vocab_size=v)))
        part.zaya.embed_tokens.weight._data = \
            whole.zaya.embed_tokens.weight._data[j * v:(j + 1) * v]
        parts.append(part.lm_logits(hidden).numpy())
    np.testing.assert_allclose(np.concatenate(parts, -1), want, atol=1e-6)


# -- the one command ---------------------------------------------------------
def test_the_cell_runs_end_to_end_and_is_correct(rehearse):
    line = rehearse(CELL, seconds=0.5, limits=TINY_LIMITS)
    assert line["correct"] is True and line["failed"] == 0, line
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    # the cell limits the two first losses too (PERF.md section 2)
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
                "gmm_roofline.train", "cca_mix_ms.train",
                "flash_cca_roofline.train", "mfu_zaya.train"} \
        & set(line["metrics"])
    assert line["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1.0


@pytest.mark.parametrize("control", ["fp8", "value_shift", "conv_dw",
                                     "conv_group", "depth_averaging"])
def test_a_control_reads_above_the_program(small, control):
    """The fp8 control and a model that leaves a part of the mathematics
    out each fail one limit at least, where the program passes all."""
    cfg, ref, tw, mix = small
    n = ref.CHECK_STEPS
    exact = tw.reference_steps(cfg, mix, 1, ref, n)
    if control == "fp8":
        prog = tw.first_steps(tw.build_step(cfg, 1, ref), cfg, mix, 1, ref,
                              n)
        sound = tw.compare(prog, exact, TINY_LIMITS)
        assert all(v["value"] <= v["limit"] for v in sound.values()), sound
        broken = tw.reference_steps(cfg, mix, 1, ref, n, rnd=ref.fp8)
    else:
        broken = tw.reference_steps(cfg, mix, 1, ref, n, parts=(control,))
    got = tw.compare(broken, exact, TINY_LIMITS)
    assert any(v["value"] > v["limit"] for v in got.values()), got


def test_a_program_without_the_value_shift_is_not_correct(small,
                                                          monkeypatch):
    """The same verdict on the timed path itself: `ops.cca_mix` handing
    both value heads the token's own."""
    from paddle_tpu.ops import cca_ops
    cfg, ref, tw, mix = small
    monkeypatch.setattr(cca_ops, "_before", lambda x: x)
    n = ref.CHECK_STEPS
    prog = tw.first_steps(tw.build_step(cfg, 2, ref), cfg, mix, 2, ref, n)
    exact = tw.reference_steps(cfg, mix, 2, ref, n)
    got = tw.compare(prog, exact, TINY_LIMITS)
    assert any(v["value"] > v["limit"] for v in got.values()), got


# -- the configuration's file --------------------------------------------------
def _published_row():
    import os
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not in this installation")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "ZAYA1-8B")


PUBLISHED = {   # the row's `config`, but for the list of layer kinds
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5,
                           "rope_theta": 10000, "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272,
}


def test_the_file_holds_the_published_row_but_for_what_reduced_names():
    spec = Spec(REPO)
    cfg = spec.data("configs", CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] == (
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json")
    cut = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 131136}
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == cut.get(key, value), key
    for key, value in cut.items():
        assert cfg["published"][key] == PUBLISHED[key] != value
    assert cfg["layer_types"] == ["hybrid"] * 5
    # the guide's floors: four layers and more, 8 experts and more, an
    # eighth of the vocabulary and more
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 262272
    assert {"cca_convolutions", "qk_mean", "value_shift", "qk_norm",
            "router", "residual", "loss", "skip_output", "rope",
            "initializer_range", "weights"} <= set(cfg["assumed"])
    assert "16 chips" in cfg["deployment"]


def test_the_file_against_the_catalogs_row():
    row = _published_row()
    assert row["config"] == {**PUBLISHED,
                             "layer_types": ["hybrid"] * 40}
    assert row["source_url"] == Spec(REPO).data("configs", CONFIG)["source"]


def test_the_program_reads_the_share_from_the_file():
    from paddle_tpu.models.zaya import ZayaConfig
    c = ZayaConfig.from_dict(Spec(REPO).data("configs", CONFIG))
    assert (c.num_experts, tuple(c.experts_held)) == (16, (0, 8))
    assert (c.vocab_size, c.num_hidden_layers) == (131136, 5)
    assert c.rope_parameters["hybrid"]["rope_theta"] == 5000000
    assert (c.router_hidden_size, c.cca_time0, c.cca_time1) == (256, 2, 2)


LAGUNA = "laguna-xs2-l5-e64.train-8k"


def test_the_cell_joins_the_shared_metrics_and_brings_its_own():
    doc = Spec(REPO).doc
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-32k", 1)
    assert all(w["chips"] == 1 for w in doc["workloads"])
    mine = {m["name"] for m in doc["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine == {
        "step_device_ms.train", "device_idle.train", "head_loss_ms.train",
        "optimizer_unfused_ms.train", "recompute_ms.train",
        "host_step_ms.train", "step_lower_s.train", "step_compile_s.train",
        "moe_ffn_ms.train", "moe_route_ms.train", "gmm_roofline.train",
        "moe_load_max_over_mean.train", "cca_mix_ms.train",
        "flash_cca_roofline.train", "mfu_zaya.train"}
    for m in doc["per_layer"]:
        if m["name"] in ("mfu.train", "flash_roofline.train",
                         "flash_window_roofline.train", "mfu_laguna.train"):
            assert CELL not in m["workloads"]
        if m["name"] in ("cca_mix_ms.train", "flash_cca_roofline.train",
                         "mfu_zaya.train"):
            assert m["workloads"] == [CELL]


def test_lagunas_cell_reports_what_it_did():
    """What `test_laguna.py` asserts of its cell's metrics, without the
    count of cells it pins at four (that file cannot be edited by the PR
    that adds the fifth: PERF.md section 7)."""
    doc = Spec(REPO).doc
    mine = {m["name"] for m in doc["per_layer"]
            if LAGUNA in m.get("workloads", [])}
    assert mine == {
        "step_device_ms.train", "device_idle.train", "head_loss_ms.train",
        "optimizer_unfused_ms.train", "recompute_ms.train",
        "host_step_ms.train", "step_lower_s.train", "step_compile_s.train",
        "moe_ffn_ms.train", "moe_route_ms.train", "gmm_roofline.train",
        "flash_window_roofline.train", "mfu_laguna.train",
        "moe_load_max_over_mean.train"}
    # at least: a later PR adds cells by files alone and cannot edit this
    assert len(doc["workloads"]) >= 5


# -- counted costs -------------------------------------------------------------
def test_flops_against_the_issues_count():
    from harness import zaya_flops
    cfg = Spec(REPO).data("configs", CONFIG)
    assert zaya_flops.held_per_token(cfg) == 0.5
    parts = zaya_flops.parts_per_token(cfg, 32768)
    assert parts["routed_experts"] == 6.0 * 5 * 0.5 * 3 * 2048 * 2048
    assert parts["attention"] == 12.0 * 5 * 1024 * (32768 + 1) / 2
    assert parts["head"] == 6.0 * 2048 * 131136
    latent = 2048 * 1536 + 1024 * 2048 + 10 * 2 * 128 * 128
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 16
    assert parts["other"] == 6.0 * 5 * (latent + router)
    assert zaya_flops.train_flops_per_token(cfg, 32768) == sum(
        parts.values())
    # the issue's arithmetic: the head 1.6 GFLOP a token, the five
    # layers' attention 1.0, their other products 0.4
    assert round(parts["head"] / 1e9, 1) == 1.6
    assert round(parts["attention"] / 1e9, 1) == 1.0
    assert round((parts["other"] + parts["routed_experts"]) / 1e9, 1) == 0.4
    # the published size: what a token meets in a whole layer
    per_layer = latent + router + 3 * 2048 * 2048
    assert round(40 * per_layer / 1e9, 2) == 0.75
