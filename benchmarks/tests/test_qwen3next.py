"""The Qwen3-Next configuration and cell at a size a CPU test can hold:
the program against the plain reference on seeded weights (logits, loss,
every gradient leaf, two AdamW steps), the reference's blocked
recurrence against the plain one, the eight expert shares and the eight
vocabulary slices against the uncut layer and head, the one command end
to end, the comparison's verdicts (the fp8 control, a model without the
delta correction, the output gate, the unit norms or `1 + w`, half a
batch, a state returned unchanged), the configuration's file against the
published row and its size check, the entries of `BENCHMARK.json`, and
the counted costs against hand counts. The readers against a trace
recorded on the chip are in `test_qwen3next_trace.py`."""
import json
import math
import os

import numpy as np
import pytest

import test_setup_metrics as pinned
from harness.spec import BENCH_DIR, REPO, Spec

CONFIG, CELL = "qwen3-next-80b-l4-e64", "qwen3-next-80b-l4-e64.train-16k"
URL = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
       "config.json")
# the tiny size's own: 64 tokens a step and weights of 0.15 make a bf16
# step's worst leaf read up to 0.05 and the controls' 0.2 and more
TINY_LIMITS = {"grad_norm_worst_leaf": 0.15, "change_norm_median_leaf": 0.02,
               "rule_gap": 1e-4}
# smaller than `tiny.TINY_MODEL` leaves it: 2 key heads on 4 value heads
# of 8, 4 heads on 2 of 16, two of eight narrow experts held from expert
# 4 on, three chosen, and draws under which every part is felt
SMALL = dict(linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=8,
             num_attention_heads=4, num_key_value_heads=2,
             partial_rotary_factor=0.5, moe_intermediate_size=32,
             shared_expert_intermediate_size=32, num_experts=2,
             expert_first=4, num_experts_per_tok=3,
             seeded_draws={"embedding": 0.3, "residual_output": 0.1,
                           "norm_weight": 0.1, "A": [0.001, 16.0],
                           "dt": [0.001, 0.1]})


@pytest.fixture
def tiny_spec(tmp_path):
    import tiny
    return Spec(tiny.make_tiny_repo(str(tmp_path / "r"), limits=TINY_LIMITS))


@pytest.fixture
def small(tiny_spec):
    """(cfg, reference module, driver module, mix) at the SMALL size."""
    cfg = tiny_spec.data("configs", CONFIG)
    cfg.update(SMALL)
    cfg["published"] = dict(cfg["published"], num_experts=8)
    return (cfg, tiny_spec.module("reference", CONFIG),
            tiny_spec.module("drivers", "qwen3next_train_window"),
            tiny_spec.data("traffic", "pretrain-16k"))


def _ids(cfg, seed=0, rows=2, seq=70):
    toks = np.random.default_rng(seed).integers(
        0, cfg["real_vocab_size"], (rows, seq + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def _ref_loss(qr, plain, cfg, ids, labels):
    import jax.numpy as jnp

    def loss(params):
        x = params[0][jnp.asarray(ids)]
        for i in range(cfg["num_hidden_layers"]):
            lo, hi = plain.bounds[i]
            x, _counts = qr.block(
                params[lo:hi], x, plain.rope(i, ids.shape[1]), cfg=cfg,
                full=plain.kind(i), rnd=qr.exact)
        return qr.head_loss(x, params[-2], params[-1], jnp.asarray(labels),
                            eps=cfg["rms_norm_eps"], rnd=qr.exact) / ids.size
    return loss


# -- the program against the reference ------------------------------------
def test_logits_loss_and_every_gradient_leaf_match_the_reference(small):
    """Rows of 70 tokens: two chunks of the rule, the second padded; the
    reference walks them token by token."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from harness import qwen3next_program, qwen3next_reference as qr
    from paddle_tpu.models import GPTPretrainingCriterion
    cfg, ref, _tw, _mix = small
    ids, labels = _ids(cfg)
    model = qwen3next_program.build_model(cfg, 5, ref)
    model.eval()
    plain = ref.Model(cfg, 5)
    assert [plain.kind(i) for i in range(4)] == [False, False, False, True]
    logits = model(pt.to_tensor(ids))
    # float32 on both sides: logits of 2 and more from weights of 0.15,
    # a state summed over 70 tokens
    np.testing.assert_allclose(logits.numpy(), plain.logits(ids),
                               atol=3e-4)
    # the counts are those of the reference's router
    x, want = plain.params[0][jnp.asarray(ids)], []
    for i in range(cfg["num_hidden_layers"]):
        x, counts = plain._blocks[plain.kind(i)](
            plain.layer(i), x, plain.rope(i, ids.shape[1]))
        want.append(np.asarray(counts))
    assert (model.expert_counts.numpy() == np.stack(want)).all()
    assert 0 < np.sum(want) < ids.size * 3 * len(want)  # some held, some not

    loss = GPTPretrainingCriterion()(logits, pt.to_tensor(labels))
    loss.backward()
    want_loss, want = jax.value_and_grad(
        _ref_loss(qr, plain, cfg, ids, labels))(plain.params)
    np.testing.assert_allclose(float(loss.numpy()), float(want_loss),
                               rtol=2e-6)
    assert len(want) == 3 * 16 + 15 + 3
    for (name, p), g in zip(model.named_parameters(), want):
        scale = float(jnp.abs(g).max())
        assert scale > 0, name
        np.testing.assert_allclose(p.grad.numpy() / scale, g / scale,
                                   atol=2e-4, err_msg=name)


def test_two_adamw_steps_match_the_reference(small):
    """The timed path's own objects in float32 (no amp): `TrainStep` on
    the program against the reference's `Trainer`, every leaf's first
    gradient norm and change after two steps."""
    from drivers.train_window import leaf_gaps
    cfg, ref, tw, mix = small
    cfg = json.loads(json.dumps(cfg))
    cfg["training"]["amp"] = {"level": "O0", "dtype": "float32"}
    cfg["training"]["optimizer"]["moment_dtype"] = "float32"
    cfg["training"]["optimizer"]["learning_rate"] = 1e-4
    mix = dict(mix, seq=70)
    step = tw.build_step(cfg, 3, ref)
    prog = tw.first_steps(step, cfg, mix, 3, ref, 2)
    plain = tw.reference_steps(cfg, mix, 3, ref, 2)
    np.testing.assert_allclose(prog["losses"], plain["losses"], rtol=5e-6)
    assert max(leaf_gaps(prog["grad_norms"], plain["grad_norms"])) < 2e-3
    assert max(leaf_gaps(prog["change_norms"], plain["change_norms"])) < 2e-3
    counts = np.asarray(step.counts[0])
    assert counts.shape == (4, 2) and counts.dtype == np.int32
    assert (counts == plain["held_counts"]).all()


@pytest.mark.parametrize("part", [(), ("delta_correction",)])
def test_the_references_blocked_recurrence_is_the_plain_one(monkeypatch,
                                                            part):
    """The nested scan (blocks of `STATE_BLOCK` tokens recomputed) gives
    the values and the gradients of one scan over all tokens."""
    import jax
    import jax.numpy as jnp
    from harness import qwen3next_reference as qr
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 48, 3, 8)) * 0.4,
                           jnp.float32) for _ in range(3))
    g = jnp.asarray(-rng.uniform(0.01, 1.0, (2, 48, 3)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, (2, 48, 3)), jnp.float32)

    def run(block):
        monkeypatch.setattr(qr, "STATE_BLOCK", block)
        return jax.value_and_grad(
            lambda *xs: jnp.sum(jnp.sin(qr.recurrence(*xs, part))),
            argnums=range(5))(q, k, v, g, beta)

    (whole, g_whole), (blocked, g_blocked) = run(48), run(8)
    np.testing.assert_allclose(blocked, whole, rtol=1e-5)
    for a, b in zip(g_blocked, g_whole):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_seeded_draws_span_the_decays_the_file_states(small):
    """A_log = log u, u in [0.001, 16); dt_bias the inverse softplus of a
    step in [0.001, 0.1): a head's decay a token lies in (0.2, 1]; norm
    weights lie around their centres."""
    cfg, ref, _tw, _mix = small
    plain = ref.Model(cfg, 9)
    by_name = dict(zip((n for n, _s, _i in plain.specs), plain.params))
    dt = np.log1p(np.exp(np.asarray(by_name["model.layers.0.gdn.dt_bias"])))
    assert (dt >= 0.001 * 0.999).all() and (dt < 0.1 * 1.001).all()
    a_log = np.asarray(by_name["model.layers.0.gdn.A_log"])
    assert np.isfinite(a_log).all()
    decay = np.exp(-np.exp(a_log) * dt)         # a token's, at a = 0
    assert math.exp(-16 * 0.1) * 0.99 <= decay.min() < decay.max() <= 1.0
    zero = np.asarray(by_name["model.layers.0.input_layernorm.weight"])
    one = np.asarray(by_name["model.layers.0.gdn.norm_weight"])
    assert abs(zero.mean()) < 0.06 and abs(one.mean() - 1) < 0.15
    assert 0.05 < zero.std() < 0.2


# -- the share -----------------------------------------------------------------
def _layer_leaves(rng, cfg, full, experts):
    """A layer's leaves in the reference's order, all `experts` held."""
    from harness import qwen3next_reference as qr
    whole = dict(cfg, num_experts=experts, expert_first=0,
                 published=dict(cfg["published"], num_experts=experts),
                 num_hidden_layers=4)
    specs = qr.layer_specs(whole, 3 if full else 0)
    return whole, [(rng.standard_normal(shape) * 0.2).astype(np.float32)
                   for _n, shape, _i in specs]


@pytest.mark.parametrize("full", [False, True], ids=["linear", "full"])
def test_eight_expert_shares_add_up_to_the_uncut_layer(small, full):
    """16 experts in eight shares of 2: the layers the eight chips
    compute, with what all compute alike (the residual stream, the
    mixer, the router, the gated shared expert) counted once, are the
    uncut reference's layer."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from harness import qwen3next_reference as qr
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextDecoderLayer)
    cfg, _ref, _tw, _mix = small
    rng = np.random.default_rng(1)
    whole, leaves = _layer_leaves(rng, cfg, full, 16)
    x = rng.standard_normal((2, 24, cfg["hidden_size"])).astype(np.float32)
    rope = qr.rope_table(24, cfg["head_dim"], {
        "rope_type": "default", "rope_theta": cfg["rope_theta"],
        "partial_rotary_factor": cfg["partial_rotary_factor"]})
    uncut, counts_want = qr.block(
        [jnp.asarray(a) for a in leaves], jnp.asarray(x), rope, cfg=whole,
        full=full, rnd=qr.exact)
    index = 3 if full else 0
    n_mix = qr.N_MIXER[full]
    outs, counts = [], []
    for share in list(range(8)) + ["alike"]:
        first = 0 if share == "alike" else 2 * share
        pt.seed(0)
        layer = Qwen3NextDecoderLayer(Qwen3NextConfig.from_dict(
            dict(whole, num_experts=2, expert_first=first)), index)
        arrays = list(leaves)
        for j in (2 + n_mix, 3 + n_mix):     # gate_up_proj, down_proj
            arrays[j] = arrays[j][first:first + 2]
            if share == "alike":             # no routed expert answers
                arrays[j] = np.zeros_like(arrays[j])
        for (_n, p), a in zip(layer.named_parameters(), arrays):
            assert tuple(p.shape) == a.shape
            p._data = jnp.asarray(a)
        y, c = layer(pt.to_tensor(x), *rope)
        outs.append(y.numpy())
        counts += c.numpy().tolist() if share != "alike" else []
    *parts, alike = outs
    np.testing.assert_allclose(sum(parts) - 7 * alike, uncut, atol=2e-5)
    assert counts == np.asarray(counts_want).tolist()
    assert sum(counts) == 48 * 3                # none dropped
    assert max(np.abs(p - alike).max() for p in parts) > 1e-3


def test_eight_vocabulary_slices_logits_are_the_uncut_heads(small):
    import paddle_tpu as pt
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)
    cfg, _ref, _tw, _mix = small
    pt.seed(1)
    whole = Qwen3NextForCausalLM(Qwen3NextConfig.from_dict(cfg))
    whole.model.norm.weight._data = pt.to_tensor(np.linspace(
        -0.3, 0.3, cfg["hidden_size"]).astype(np.float32))._data
    hidden = pt.to_tensor(np.random.default_rng(2).standard_normal(
        (2, 8, cfg["hidden_size"])).astype(np.float32))
    want = whole.lm_logits(hidden).numpy()
    v = cfg["vocab_size"] // 8
    parts = []
    for j in range(8):
        part = Qwen3NextForCausalLM(Qwen3NextConfig.from_dict(
            dict(cfg, vocab_size=v)))
        part.lm_head.weight._data = \
            whole.lm_head.weight._data[:, j * v:(j + 1) * v]
        parts.append(part.lm_logits(hidden).numpy())
    np.testing.assert_allclose(np.concatenate(parts, -1), want, atol=1e-6)


# -- the one command ---------------------------------------------------------
def test_the_cell_runs_end_to_end_and_is_correct(rehearse):
    line = rehearse(CELL, seconds=0.5, limits=TINY_LIMITS)
    assert line["correct"] is True and line["failed"] == 0, line
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert set(line["compared"]) == {"loss_step1", "loss_step2",
                                     "grad_norm_worst_leaf",
                                     "change_norm_median_leaf", "rule_gap"}


def test_a_traced_rehearsal_reads_what_a_cpu_can_and_does_not_raise(
        rehearse):
    line = rehearse(CELL, seconds=0.5, trace=1, limits=TINY_LIMITS)
    assert line["correct"] is True
    # no TPU plane in a CPU trace: every device reader returns nothing;
    # the program's own counters are read all the same
    assert not {"gdn_mixer_ms.train", "gdn_state_ms.train",
                "gdn_state_roofline.train", "flash_d256_roofline.train",
                "mfu_qwen3next.train", "moe_ffn_ms.train",
                "rope_ms.train"} & set(line["metrics"])
    assert line["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1.0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        small, monkeypatch):
    """The driver's `compare` on the timed path's own step, at the small
    size: a state that does not move reads 1 on the median leaf."""
    from paddle_tpu.jit import TrainStep
    cfg, ref, tw, mix = small
    real_build = TrainStep._build

    def build(self, donate):
        fn = real_build(self, False)

        class Unchanged:
            pending = False

            def __call__(_self, params, opt_states, *rest):
                out = fn(params, opt_states, *rest)
                return (out[0], params, opt_states) + tuple(out[3:])

        return Unchanged()

    monkeypatch.setattr(TrainStep, "_build", build)
    n = ref.CHECK_STEPS
    prog = tw.first_steps(tw.build_step(_float32(cfg), 1, ref), cfg, mix, 1,
                          ref, n)
    got = tw.compare(prog, tw.reference_steps(cfg, mix, 1, ref, n),
                     TINY_LIMITS)
    assert got["change_norm_median_leaf"]["value"] > 0.9
    from harness import runlib
    assert runlib.judge(got) is False


def test_a_part_of_the_batch_left_out_is_not_correct(small, monkeypatch):
    from harness import runlib
    from paddle_tpu.jit import TrainStep
    cfg, ref, tw, mix = small
    real = TrainStep.__call__

    def call(self, ids, labels):
        half = len(ids) // 2
        return real(self, ids[:half], labels[:half])

    monkeypatch.setattr(TrainStep, "__call__", call)
    n = ref.CHECK_STEPS
    prog = tw.first_steps(tw.build_step(_float32(cfg), 1, ref), cfg, mix, 1,
                          ref, n)
    got = tw.compare(prog, tw.reference_steps(cfg, mix, 1, ref, n),
                     {"loss": 1.8e-5, **TINY_LIMITS})
    assert runlib.judge(got) is False
    assert got["loss_step1"]["value"] > 1.8e-5


def _float32(cfg):
    """The program without amp. In bfloat16 at this size (140 tokens,
    three of eight experts a token, weights of 0.15) a handful of top-3
    choices flip against the float32 reference and the worst gradient
    leaf reads 0.2 to 2.4 (the router's, the shared gate's): no limit
    lies between the program and the controls here. The bfloat16 program
    is held to its limits at the timed size, on the chip
    (`tools/limits_qwen3next.py`); here the verdicts are checked on the
    float32 program."""
    cfg = json.loads(json.dumps(cfg))
    cfg["training"]["amp"] = {"level": "O0", "dtype": "float32"}
    return cfg


@pytest.mark.parametrize("control", ["fp8", "delta_correction",
                                     "output_gate", "qk_unit_norm",
                                     "zero_centered", "rule_float32"])
def test_a_control_reads_above_the_program(small, control):
    """The fp8 control, a model that leaves a part of the mathematics
    out and one whose delta rule runs in bfloat16 each fail one limit at
    least, where the program passes all. The rule in bfloat16 fails by
    `rule_gap` and by nothing else: rounding that goes up as often as
    down moves no leaf's norm."""
    cfg, ref, tw, mix = small
    mix = dict(mix, seq=70)
    n = ref.CHECK_STEPS
    exact = tw.reference_steps(cfg, mix, 1, ref, n)
    if control == "fp8":
        prog = tw.first_steps(tw.build_step(_float32(cfg), 1, ref), cfg,
                              mix, 1, ref, n)
        prog["rule_gaps"] = tw.rule_gaps(cfg, mix, 1, ref)
        sound = tw.compare(prog, exact, TINY_LIMITS)
        assert set(sound) == {"rule_gap", "grad_norm_worst_leaf",
                              "change_norm_median_leaf"}
        assert all(v["value"] <= v["limit"] for v in sound.values()), sound
        broken = tw.reference_steps(cfg, mix, 1, ref, n, rnd=ref.fp8)
    else:
        broken = tw.reference_steps(cfg, mix, 1, ref, n, parts=(control,))
    if control == "rule_float32":
        broken["rule_gaps"] = tw.rule_gaps(cfg, mix, 1, ref,
                                           parts=(control,))
    got = tw.compare(broken, exact, TINY_LIMITS)
    failed = {k for k, v in got.items() if not v["value"] <= v["limit"]}
    assert failed, got
    if control == "rule_float32":
        assert failed == {"rule_gap"} and got["rule_gap"]["value"] > 1e-3


def test_a_program_without_the_delta_correction_is_not_correct(
        small, monkeypatch):
    """The same verdict on the timed path itself: the chunk preparation
    with T = I and W = 0 (V' = beta V: nothing is subtracted from a
    token's value, inside its chunk or from the state)."""
    import jax.numpy as jnp
    from paddle_tpu.kernels.pallas import gated_delta as gd
    cfg, ref, tw, mix = small
    mix = dict(mix, seq=70)
    real = gd.prepare

    def prepare(*args):
        U, W, *rest = real(*args)
        return (U, W * 0.0, *rest)

    monkeypatch.setattr(gd, "prepare", prepare)
    monkeypatch.setattr(gd, "_inverse", lambda A: jnp.broadcast_to(
        jnp.eye(A.shape[-1], dtype=A.dtype), A.shape) + 0.0 * A)
    n = ref.CHECK_STEPS
    prog = tw.first_steps(tw.build_step(_float32(cfg), 1, ref), cfg, mix, 1,
                          ref, n)
    exact = tw.reference_steps(cfg, mix, 1, ref, n)
    got = tw.compare(prog, exact, TINY_LIMITS)
    assert any(v["value"] > v["limit"] for v in got.values()), got


# -- the configuration's file --------------------------------------------------
def _published_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not in this installation")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "Qwen3-Next-80B-A3B-Instruct")


PUBLISHED = {   # the row's `config`
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
CUT = {"num_hidden_layers": 4, "num_experts": 64, "vocab_size": 18992}


def test_the_file_holds_the_published_row_but_for_what_reduced_names():
    spec = Spec(REPO)
    cfg = spec.data("configs", CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] == URL
    assert entry["reduced"] == cfg["reduced"] == list(CUT)
    for key, value in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, value), key
    for key, value in CUT.items():
        assert cfg["published"][key] == PUBLISHED[key] != value
    # the guide's floors: one whole period of four layers, 8 experts and
    # more, an eighth of the vocabulary and more
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 151936
    assert {"layer_rule", "mtp", "gated_delta_net", "gated_attention",
            "router", "weights", "learning_rate"} <= set(cfg["assumed"])
    assert "96 chips" in cfg["deployment"] and "79.67B" in cfg["size_check"]


def test_the_file_against_the_catalogs_row():
    row = _published_row()
    assert row["config"] == PUBLISHED
    assert row["source_url"] == URL


def test_the_size_check_by_the_references_own_parameter_list():
    """Whole, the reference's forms give the published 80B; this share
    1028.3M, 805M of them routed experts (the issue's count)."""
    from harness import qwen3next_reference as qr
    cfg = Spec(REPO).data("configs", CONFIG)

    def count(c, only=""):
        return sum(math.prod(s) for n, s, _i in qr.param_specs(c)
                   if only in n)

    assert count(cfg) == 1028320320
    assert round(count(cfg) / 1e6, 1) == 1028.3
    experts = count(cfg, "moe.gate_up_proj") + count(cfg, "moe.down_proj")
    assert round(experts / 1e6) == 805
    whole = dict(cfg, **{k: PUBLISHED[k] for k in CUT})
    del whole["published"]
    assert round(count(whole) / 1e9, 2) == 79.67
    layer = {n.split(".", 3)[3]: math.prod(s)
             for n, s, _i in qr.layer_specs(cfg, 0)}
    mixer = sum(v for k, v in layer.items() if k.startswith("gdn."))
    assert round(mixer / 1e6, 2) == 33.72
    full = sum(math.prod(s) for n, s, _i in qr.layer_specs(cfg, 3)
               if ".attn." in n)
    assert round(full / 1e6, 2) == 27.26


def test_the_program_reads_the_share_from_the_file():
    from paddle_tpu.models.qwen3_next import Qwen3NextConfig
    c = Qwen3NextConfig.from_dict(Spec(REPO).data("configs", CONFIG))
    assert (c.num_experts, tuple(c.experts_held)) == (512, (0, 64))
    assert (c.vocab_size, c.num_hidden_layers) == (18992, 4)
    assert c.residual_depth == 48
    assert (c.rope_theta, c.partial_rotary_factor) == (10000000, 0.25)
    assert [c.is_full(i) for i in range(4)] == [False, False, False, True]


# -- the entries of BENCHMARK.json ----------------------------------------------
OWN = {"gdn_mixer_ms.train", "gdn_state_ms.train",
       "gdn_state_roofline.train", "flash_d256_roofline.train",
       "mfu_qwen3next.train",
       "gdn_prepare_ms.train"}
JOINED = {"step_device_ms.train", "device_idle.train", "head_loss_ms.train",
          "optimizer_unfused_ms.train", "recompute_ms.train",
          "host_step_ms.train", "step_lower_s.train", "step_compile_s.train",
          "moe_ffn_ms.train", "moe_route_ms.train", "gmm_roofline.train",
          "moe_load_max_over_mean.train", "rope_ms.train"}
LAGUNA, ZAYA = "laguna-xs2-l5-e64.train-8k", "zaya1-8b-l5-e8.train-32k"


def test_the_cell_joins_the_shared_metrics_and_brings_its_own():
    spec = Spec(REPO)
    doc = spec.doc
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-16k", 1)
    assert all(w["chips"] == 1 for w in doc["workloads"])
    # at least: a later PR adds cells by files alone and cannot edit this
    assert len(doc["workloads"]) >= 6
    mine = {m["name"] for m in doc["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine == JOINED | OWN | set(pinned.READERS)
    assert CELL in next(m for m in doc["end_to_end"]
                        if m["name"] == "train_tok_s_chip")["workloads"]
    for m in doc["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["unit"] in ("ms", "%")
            assert m["moves"] == "train_tok_s_chip"
            assert callable(spec.module("layer_metrics", m["name"]).read)
        elif CELL in m["workloads"]:
            # a new cell is the last of a list it joins
            assert m["workloads"][-1] == CELL
        if m["name"] in ("mfu.train", "flash_roofline.train",
                         "flash_window_roofline.train", "mfu_laguna.train",
                         "flash_cca_roofline.train", "cca_mix_ms.train",
                         "mfu_zaya.train", "ssm_scan_ms.train"):
            assert CELL not in m["workloads"]
    # new entries are the list's last, in the issue's order, then the
    # review's
    assert [m["name"] for m in doc["per_layer"][-6:]] == [
        "gdn_mixer_ms.train", "gdn_state_ms.train",
        "gdn_state_roofline.train", "flash_d256_roofline.train",
        "mfu_qwen3next.train", "gdn_prepare_ms.train"]


def test_the_rotarys_entry_stands_as_it_was_with_this_cell_at_its_end():
    """Every assertion of `test_rope_trace.py::
    test_the_entry_of_benchmark_json`, with the cell that joined the
    list's end."""
    spec = Spec(REPO)
    doc = spec.doc
    entry, = [m for m in doc["per_layer"] if m["name"] == "rope_ms.train"]
    assert entry == {
        "name": "rope_ms.train", "unit": "ms", "better": "lower",
        "source": "device_trace",
        "layer": next(m["layer"] for m in doc["per_layer"]
                      if m["name"] == "cca_mix_ms.train"),
        "moves": "train_tok_s_chip", "workloads": [LAGUNA, ZAYA, CELL]}
    for cell in (LAGUNA, ZAYA, CELL):
        assert "rope_ms.train" in {
            m["name"] for m in spec.metrics("per_layer", cell)}
    for cell in pinned.CELLS:
        assert "rope_ms.train" not in {
            m["name"] for m in spec.metrics("per_layer", cell)}
    assert callable(spec.module("layer_metrics", "rope_ms.train").read)


@pytest.mark.parametrize("name", sorted(pinned.READERS))
def test_the_set_up_entries_stand_as_they_were_with_this_cell_at_their_end(
        name):
    """Every assertion of `test_rope_trace.py::
    test_the_set_up_entries_stand_as_they_were`, with the cell that
    joined each list's end."""
    doc = Spec(REPO).doc
    entry, = [m for m in doc["per_layer"] if m["name"] == name]
    unit, better = pinned.READERS[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["better"]) == (unit, better)
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "setup_s"
    assert entry["workloads"] == pinned.CELLS + [CELL]
    assert 1 <= len(entry["layer"]) <= 200 and "\n" not in entry["layer"]
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index("setup_import_s.train")
    assert names[first:first + 7] == [
        "setup_import_s.train", "setup_build_s.train", "step_trace_s.train",
        "step_first_run_s.train", "setup_other_programs_s.train",
        "setup_named_share.train", "rope_ms.train"]
    assert os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics",
                                       name + ".py"))
    assert any(m["name"] == "setup_s" for m in doc["end_to_end"])


@pytest.mark.parametrize("cell,own", [
    (LAGUNA, {"flash_window_roofline.train", "mfu_laguna.train"}),
    (ZAYA, {"cca_mix_ms.train", "flash_cca_roofline.train",
            "mfu_zaya.train"})])
def test_the_cells_before_report_what_they_did(cell, own):
    doc = Spec(REPO).doc
    mine = {m["name"] for m in doc["per_layer"]
            if cell in m.get("workloads", [])}
    assert mine == JOINED | own
    for m in doc["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [cell]


# -- counted costs -------------------------------------------------------------
def test_flops_against_the_issues_count():
    from harness import qwen3next_flops as flops
    cfg = Spec(REPO).data("configs", CONFIG)
    assert flops.held_per_token(cfg) == 1.25
    assert flops.layers(cfg) == (3, 1)
    parts = flops.parts_per_token(cfg, 16384)
    gdn = 2048 * (12288 + 64) + 4096 * 2048
    assert parts["gdn_projections"] == 6.0 * 3 * gdn
    attn = 2048 * (16 * 512 + 2 * 2 * 256) + 4096 * 2048
    assert parts["attention_projections"] == 6.0 * attn
    assert parts["attention"] == 12.0 * 16 * 256 * (16384 + 1) / 2
    expert = 3 * 2048 * 512
    assert parts["experts"] == 6.0 * 4 * (
        2048 * 512 + expert + 2048 + 1.25 * expert)
    assert parts["head"] == 6.0 * 2048 * 18992
    # the rule, a chunk and head forward: three products with the state,
    # the masked one, K K^T and Q K^T, U and W, six products of the
    # inverse; three times that for both passes, 32 heads, a token
    chunk = (3 * 2 * 64 * 128 * 128 + 2 * 64 * 64 * 128
             + 2 * 2 * 64 * 64 * 128 + 2 * 2 * 64 * 64 * 128
             + 6 * 2 * 64 ** 3)
    assert parts["delta_rule"] == 3 * 3.0 * 32 * chunk / 64
    assert flops.train_flops_per_token(cfg, 16384) == sum(parts.values())
    # the issue's arithmetic: the three mixers 0.68 GFLOP a token with
    # their rule, the attention layer 0.57 of which 0.40 is the causal
    # product, the four expert layers 0.20, the head 0.23
    assert round((parts["gdn_projections"] + parts["delta_rule"]) / 1e9,
                 2) in (0.67, 0.68)
    assert round(parts["attention"] / 1e9, 2) == 0.40
    assert round((parts["attention"] + parts["attention_projections"])
                 / 1e9, 2) == 0.57
    assert round(parts["experts"] / 1e9, 2) == 0.20
    assert round(parts["head"] / 1e9, 2) == 0.23
    total = flops.train_flops_per_token(cfg, 16384)
    assert 1.66 <= total / 1e9 <= 1.68 and 27.2 <= total * 16384 / 1e12 <= 27.5


def test_state_pass_costs_against_hand_counts():
    rule = Spec(REPO).module("kernel_costs", "gated_delta")
    assert rule.classify("a/layers/1/gdn/delta_rule/gdn_state_fwd") == "fwd"
    assert rule.classify("a/layers/1/gdn/delta_rule/gdn_state_bwd") == "bwd"
    assert rule.classify("a/layers/3/attn/flash_fwd") is None
    # 2 heads x 3 chunks of 4 tokens, keys of 8 and values of 16
    ops, nbytes = rule.cost("fwd", 2, 3, 4, 8, 16)
    assert ops == 6 * (3 * 2 * 4 * 8 * 16 + 2 * 4 * 4 * 16)
    operands = (4 * 16 + 3 * 4 * 8 + 4 * 4 + 1) * 4
    assert nbytes == 6 * (operands + 4 * 16 * 4 + 8 * 16 * 4)
    ops, nbytes = rule.cost("bwd", 2, 3, 4, 8, 16)
    assert ops == 6 * (6 * 2 * 4 * 8 * 16 + 2 * 2 * 4 * 4 * 16)
    assert nbytes == 6 * (2 * operands + 4 * 16 * 4 + 8 * 16 * 4)
    # at the cell's shape the state a chunk (64 KB) is a quarter of the
    # forward's bytes and the bytes bind: 2.4 ms at 819 GB/s against
    # 0.3 ms of products at 197 TFLOP/s
    ops, nbytes = rule.cost("fwd", 32, 256, 64, 128, 128)
    assert 0.25 < 8192 * 65536 / nbytes < 0.3
    assert nbytes / 819e9 > 5 * ops / 197e12
