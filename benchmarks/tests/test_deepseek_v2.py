"""The DeepSeek-V2 configuration and cell at a size a CPU test can hold:
the timed path's own objects against the plain reference on seeded
weights (two AdamW steps, every leaf, the balance term), latent
attention's core against the reference's blocks, the shares of a layer
adding up to the uncut layer, the one command end to end, the
comparison's verdicts with `mla_gap` (every control of
`tools/limits_deepseek_v2.py`, a state returned unchanged), the
configuration's file against the published row and its size check, the
entries of `BENCHMARK.json`, and the counted operations against a count
by hand. The program's model against its equations is
`tests/test_deepseek_v2_model.py`; the readers against a trace recorded
on the chip are in `test_deepseek_v2_trace.py`."""
import json
import os
import sys

import numpy as np
import pytest

import test_setup_metrics as pinned
from conftest import load_run
from harness.spec import BENCH_DIR, REPO, Spec

sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))

CONFIG, CELL = "deepseek-v2-lite-e8", "deepseek-v2-lite-e8.train-32k"
TRAFFIC = "pretrain-32k-mla"
URL = ("https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
       "config.json")
# the tiny size's own: 80 tokens a step in float32
TINY_LIMITS = {"loss": 1e-5, "grad_norm_worst_leaf": 0.01,
               "change_norm_median_leaf": 0.003, "mla_gap": 1e-4}
# smaller than `tiny.TINY_MODEL` leaves it: 3 layers (one dense), 4 heads
# of 16 + 8 on a latent of 32, 4 of 16 experts held, and draws under
# which every part is felt
SMALL = dict(num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
             moe_intermediate_size=32, n_routed_experts=4, num_experts=4,
             num_experts_per_tok=4, aux_loss_alpha=0.05,
             published={"num_hidden_layers": 27, "n_routed_experts": 16,
                        "vocab_size": 102400},
             seeded_draws={"embedding": 1.0, "residual_output": 0.1,
                           "norm_weight": 0.1})


@pytest.fixture(scope="module")
def tiny_spec(tmp_path_factory):
    import tiny
    return Spec(tiny.make_tiny_repo(str(tmp_path_factory.mktemp("r")),
                                    limits=TINY_LIMITS))


@pytest.fixture(scope="module")
def small(tiny_spec):
    """(cfg, reference module, driver module, mix) at the SMALL size, the
    program in float32 (the bfloat16 program is held to its limits at
    the timed size, on the chip: `tools/limits_deepseek_v2.py`)."""
    cfg = tiny_spec.data("configs", CONFIG)
    cfg.update(SMALL)
    cfg["training"]["amp"] = {"level": "O0", "dtype": "float32"}
    cfg["training"]["optimizer"]["moment_dtype"] = "float32"
    return (cfg, tiny_spec.module("reference", CONFIG),
            tiny_spec.module("drivers", "deepseek_v2_train_window"),
            dict(tiny_spec.data("traffic", TRAFFIC), seq=40))


@pytest.fixture(scope="module")
def sides(small):
    """(the program's first steps, the reference's) on seed 1, made once
    for the tests that read them."""
    cfg, ref, tw, mix = small
    n = ref.CHECK_STEPS
    step = tw.build_step(cfg, 1, ref)
    prog = tw.first_steps(step, cfg, mix, 1, ref, n)
    prog["balance"] = [float(np.asarray(b)) for _c, b in step.counts]
    prog["counts"] = np.asarray(step.counts[0][0])
    prog["mla_gaps"] = _float32_gaps(cfg, mix, ref, tw)
    return prog, tw.reference_steps(cfg, mix, 1, ref, n)


def _float32_gaps(cfg, mix, ref, tw):
    """`mla_gaps` of the program's core on float32 operands (the timed
    run hands it bfloat16, as amp does)."""
    import jax.numpy as jnp
    real = jnp.bfloat16
    try:
        jnp.bfloat16 = jnp.float32
        return tw.mla_gaps(cfg, mix, 1, ref)
    finally:
        jnp.bfloat16 = real


# -- the program against the reference ------------------------------------
def test_two_adamw_steps_match_the_reference(small, sides):
    """The timed path's own objects in float32: `TrainStep` on the
    program against the reference's `Trainer`, every leaf's first
    gradient norm and change after two steps, the counts and the balance
    term."""
    from drivers.train_window import leaf_gaps
    cfg, ref, tw, _mix = small
    prog, plain = sides
    np.testing.assert_allclose(prog["losses"], plain["losses"], rtol=5e-6)
    assert max(leaf_gaps(prog["grad_norms"], plain["grad_norms"])) < 2e-4
    assert max(leaf_gaps(prog["change_norms"], plain["change_norms"])) < 1e-3
    np.testing.assert_allclose(prog["balance"], plain["balance"], rtol=1e-5)
    np.testing.assert_array_equal(prog["counts"], plain["held_counts"])
    assert prog["counts"].shape == (2, 4)
    assert len(prog["grad_norms"]) == len(ref.param_specs(cfg)) == \
        1 + 10 + 2 * 13 + 2
    got = tw.compare(prog, plain, TINY_LIMITS)
    assert set(got) == {"loss_step1", "loss_step2", "grad_norm_worst_leaf",
                        "change_norm_median_leaf", "mla_gap"}
    assert all(v["value"] <= v["limit"] for v in got.values()), got


def test_the_loss_is_the_cross_entropy_plus_alpha_times_the_balance(small,
                                                                    sides):
    cfg, ref, tw, mix = small
    _prog, plain = sides
    without = tw.reference_steps(cfg, mix, 1, ref, 1,
                                 parts=("balance_loss",))
    assert plain["losses"][0] - without["losses"][0] == pytest.approx(
        cfg["aux_loss_alpha"] * plain["balance"][0], rel=1e-4)
    assert 0.9 < plain["balance"][0] < 2.0


def test_the_core_is_the_references_blocks(small):
    """`mla_gaps`: the program's one attention call with a key in two
    parts against `latent_core`, o and the five gradients."""
    cfg, ref, tw, mix = small
    gaps = _float32_gaps(cfg, mix, ref, tw)
    assert set(gaps) == set(tw.MLA_OUTPUTS) == {"o", "dq", "dq_pe", "dk",
                                                "dk_pe", "dv"}
    assert max(gaps.values()) < 1e-5, gaps
    low = tw.mla_gaps(cfg, mix, 1, ref)         # bfloat16 in, as timed
    assert 1e-4 < max(low.values()) < 0.05, low
    for parts in (("shared_rotary_key",), ("mscale",)):
        broken = tw.mla_gaps(cfg, mix, 1, ref, parts=parts)
        assert min(broken.values()) > 0.01, (parts, broken)
    assert max(tw.mla_gaps(cfg, mix, 1, ref, rnd=ref.fp8).values()) > 0.01


def test_the_shares_add_up(small):
    """The shares' routed parts, plus the shared experts counted once,
    are the uncut layer: eight shares of 2 experts against one layer
    holding all 16, the same seeded router, the balance term whole on
    every share."""
    import jax
    import jax.numpy as jnp
    from harness import deepseek_v2_reference as dsr
    cfg, _ref, _tw, _mix = small
    whole_cfg = dict(cfg, n_routed_experts=16, expert_first=0)
    whole = dsr.Model(whole_cfg, 5)
    lay = whole.layer(1)[dsr.N_MIXER:]      # norm, gate_up, down, router, ...
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 64), jnp.float32)
    kw = dict(rnd=dsr.exact, sparse=True)
    (y_whole, term), counts = dsr.ffn_sublayer(lay, x, cfg=whole_cfg, **kw)
    shared = dsr.swiglu(lay[4:7], dsr.norm(x, lay[0], 1e-6), dsr.exact)
    routed = jnp.zeros_like(x)
    held = []
    for first in range(0, 16, 2):
        share = [lay[0], lay[1][first:first + 2], lay[2][first:first + 2],
                 *lay[3:]]
        (y, t), c = dsr.ffn_sublayer(
            share, x, cfg=dict(cfg, expert_first=first), **kw)
        routed = routed + (y - x - shared)
        held.append(np.asarray(c))
        assert float(t) == pytest.approx(float(term), rel=1e-6)
    np.testing.assert_allclose(x + routed + shared, y_whole, atol=2e-6)
    np.testing.assert_array_equal(np.concatenate(held), np.asarray(counts))
    assert int(np.asarray(counts).sum()) == 2 * 24 * 4


def test_the_seeded_draws_are_the_files(small):
    cfg, ref, _tw, _mix = small
    plain = ref.Model(cfg, 9)
    by_name = dict(zip((n for n, _s, _i in plain.specs), plain.params))
    norms = [np.asarray(v) for k, v in by_name.items() if "norm" in k]
    assert len(norms) == 3 * 3 + 1
    assert all(abs(w.mean() - 1) < 0.08 and 0.04 < w.std() < 0.2
               for w in norms)
    assert 0.8 < float(np.std(by_name["model.embed_tokens.weight"])) < 1.2
    assert float(np.std(by_name["model.layers.1.moe.down_proj"])) == \
        pytest.approx(0.1, rel=0.1)


# -- the one command ---------------------------------------------------------
def test_the_cell_runs_end_to_end_and_is_correct(tmp_path, capsys):
    """`conftest.rehearse`, keeping the notes' line beside the result's.
    bfloat16 at 64 tokens: the limits here only hold the control flow."""
    import tiny
    repo = tiny.make_tiny_repo(str(tmp_path / "repo"))
    capsys.readouterr()
    load_run().main(["--workload", CELL, "--seed", "3000000007",
                     "--seconds", "0.5", "--trace", "0"], repo=repo,
                    require_chip=False)
    out = [json.loads(ln) for ln in
           capsys.readouterr().out.strip().splitlines() if ln[:1] == "{"]
    line, notes = out[-1], out[-2]["notes"]
    assert line["correct"] is True and line["failed"] == 0, line
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert set(line["compared"]) == {"loss_step1", "loss_step2",
                                     "grad_norm_worst_leaf",
                                     "change_norm_median_leaf", "mla_gap"}
    assert len(notes["program_losses"]) == len(
        notes["reference_losses"]) == 2
    assert set(notes["mla_gaps"]) == {"o", "dq", "dq_pe", "dk", "dk_pe",
                                      "dv"}
    moe = notes["moe"]
    for key in ("moe.assignments_held", "moe.load_max_over_mean",
                "moe.aux_loss", "count_shift_share_by_layer"):
        assert key in moe, key
    assert len(moe["assignments_held_first_last"]) == 2
    assert len(moe["aux_loss_first_last"]) == 2
    assert len(moe["aux_loss_check_steps"]) == len(
        moe["aux_loss_reference"]) == 2
    assert notes["paths"]["attention"].startswith("xla: no TPU Pallas")
    assert "sequence balance term" in notes["paths"]["moe"]
    assert notes["paths"]["flash_kept"].startswith("o and lse kept")
    assert notes["steps"] == line["attempted"] >= 1


def test_a_traced_rehearsal_reads_what_a_cpu_can_and_does_not_raise(
        rehearse):
    line = rehearse(CELL, seconds=0.5, trace=1)
    assert line["correct"] is True
    # no TPU plane in a CPU trace: every device reader returns nothing
    assert not {"mfu_deepseek_v2.train", "mla_latent_ms.train",
                "flash_mla_roofline.train", "rope_ms.train",
                "step_device_ms.train"} & set(line["metrics"])
    assert "moe_load_max_over_mean.train" in line["metrics"]


# -- the comparison's verdicts ---------------------------------------------
@pytest.mark.parametrize("control", [
    "fp8", "shared_rotary_key", "mscale", "latent_norm",
    "weights_as_scored", "balance_loss"])
def test_a_control_reads_above_the_program(small, sides, control):
    """Every control of `tools/limits_deepseek_v2.py` fails one limit at
    least, where the program passes all: the reference in fp8; the
    rotary key a head instead of shared; the scale without mscale^2; the
    latent's norm left out; weights normalised over the chosen; the
    balance loss left out."""
    import limits_deepseek_v2 as tool
    cfg, ref, tw, mix = small
    assert control in tool.CONTROLS
    assert set(tool.CONTROLS) == {"fp8"} | set(tool.PARTS)
    assert set(tool.CORE_PARTS) == {"shared_rotary_key", "mscale"}
    prog, exact = sides
    sound = tw.compare(prog, exact, TINY_LIMITS)
    assert all(v["value"] <= v["limit"] for v in sound.values()), sound
    n = ref.CHECK_STEPS
    kw = {"rnd": ref.fp8} if control == "fp8" else {"parts": (control,)}
    broken = tw.reference_steps(cfg, mix, 1, ref, n, **kw)
    if control in tool.CORE_PARTS or control == "fp8":
        broken["mla_gaps"] = tw.mla_gaps(
            cfg, mix, 1, ref, parts=kw.get("parts", ()),
            rnd=kw.get("rnd"))
    got = tw.compare(broken, exact, TINY_LIMITS)
    failed = {k for k, v in got.items() if not v["value"] <= v["limit"]}
    assert failed, got
    if control in tool.CORE_PARTS:
        assert "mla_gap" in failed
    if control == "balance_loss":
        # the first forward's hidden states are the same: the loss lacks
        # its term, and of the gradients the routers' differ most
        assert "loss_step1" in failed
        names = [n for n, _s, _i in ref.param_specs(cfg)]
        worst = tw.worst_leaves(broken, exact, names)["grad_norms"][0][0]
        assert worst.endswith("moe.router.weight")


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        small, sides, monkeypatch):
    from harness import runlib
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
    prog = tw.first_steps(tw.build_step(cfg, 1, ref), cfg, mix, 1, ref,
                          ref.CHECK_STEPS)
    got = tw.compare(prog, sides[1], TINY_LIMITS)
    assert got["change_norm_median_leaf"]["value"] > 0.9
    assert runlib.judge(got) is False


# -- the configuration's file --------------------------------------------------
def _published_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not in this installation")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "DeepSeek-V2-Lite")


PUBLISHED = {   # the row's `config`
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "greedy",
    "v_head_dim": 128, "vocab_size": 102400,
}
CUT = {"num_hidden_layers": 7, "n_routed_experts": 8, "vocab_size": 12800}


def test_the_file_holds_the_published_row_but_for_what_reduced_names():
    spec = Spec(REPO)
    cfg = spec.data("configs", CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] == URL
    assert entry["reduced"] == cfg["reduced"] == list(CUT)
    assert entry["file"] == "benchmarks/configs/deepseek-v2-lite-e8.json"
    for key, value in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, value), key
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64,
                                "vocab_size": 102400}
    # the guide's floors: the dense layer and four sparse ones, 8
    # experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] >= 5 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 == 102400 == 8 * cfg["real_vocab_size"]
    # the shared readers' key beside the config.json's
    assert cfg["num_experts"] == cfg["n_routed_experts"]
    assert "num_experts" in cfg["harness_keys"]
    assert {"aux_loss_alpha", "rotary_layout", "yarn", "shared_experts",
            "router", "weights", "learning_rate"} <= set(cfg["assumed"])
    for word in ("32 chips", "4 pipeline stages", "8 chips",
                 "rank 0 of the first stage", "nothing stands in"):
        assert word in cfg["deployment"], word
    assert cfg["seeded_draws"]["residual_output"] == pytest.approx(
        0.02 / (2 * 27) ** 0.5)
    assert cfg["aux_loss_alpha"] == 0.001
    # by membership: a later PR appends its configuration by files alone
    assert entry in spec.doc["configs"][7:]


def test_the_file_against_the_catalogs_row():
    row = _published_row()
    assert row["config"] == PUBLISHED
    assert row["source_url"] == URL


def test_the_size_check_by_the_references_own_parameter_list():
    """Held: 735.9M, 415.2M of them routed experts; whole: the published
    15.7B."""
    spec = Spec(REPO)
    ref, cfg = spec.module("reference", CONFIG), spec.data("configs", CONFIG)
    held = ref.n_params(cfg)
    assert held == 735872512 and "735.873M" in cfg["size_check"]
    whole = ref.n_params({**cfg, **cfg["published"]})
    assert round(whole / 1e9, 3) == 15.706 and "15.706B" in cfg["size_check"]
    routed = 6 * 8 * 3 * 2048 * 1408
    assert routed == 415236096 and "415.236M" in cfg["size_check"]
    attention = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    assert attention == 13763072 and "13.763M" in cfg["size_check"]
    # 8 B a parameter of arguments: over a third of the chip's 16.9 GB
    assert 0.25 * 16.9e9 < 8 * held < 0.40 * 16.9e9


def test_the_program_reads_the_cut_from_the_file():
    from paddle_tpu.models.deepseek_v2 import DeepseekV2Config
    c = DeepseekV2Config.from_dict(Spec(REPO).data("configs", CONFIG))
    assert (c.num_hidden_layers, c.n_routed_experts, c.experts_held) == (
        7, 64, (0, 8))
    assert (c.vocab_size, c.hidden_size, c.intermediate_size,
            c.moe_intermediate_size) == (12800, 2048, 10944, 1408)
    assert (c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, c.kv_lora_rank) == (16, 128, 64, 128, 512)
    assert c.softmax_scale == pytest.approx(0.11472, abs=1e-5)
    assert c.out_std == pytest.approx(0.0027217, rel=1e-4)
    assert c.aux_loss_alpha == 0.001 and not c.norm_topk_prob


def test_the_cells_file_states_its_limits_and_their_readings():
    spec = Spec(REPO)
    cell = spec.data("cells", CELL)
    assert set(cell["limits"]) == {"loss", "grad_norm_worst_leaf",
                                   "change_norm_median_leaf", "mla_gap"}
    assert cell["limits"]["loss"] == 1.8e-05        # the accepted cells'
    for word in ("fp8", "rotary key", "mscale", "latent", "normalised",
                 "balance", "my chip runs, PR 46"):
        assert word in cell["readings"], word
    mix = spec.data("traffic", TRAFFIC)
    assert (mix["batch"], mix["seq"], mix["driver"]) == (
        1, 32768, "deepseek_v2_train_window")
    assert (mix["trace_after_s"], mix["trace_for_s"]) == (3.0, 10.0)


# -- the entries of BENCHMARK.json ----------------------------------------------
OWN = ["mla_latent_ms.train", "flash_mla_roofline.train",
       "mfu_deepseek_v2.train"]
JOINED = {"step_device_ms.train", "device_idle.train", "head_loss_ms.train",
          "optimizer_unfused_ms.train", "recompute_ms.train",
          "host_step_ms.train", "step_lower_s.train", "step_compile_s.train",
          "moe_ffn_ms.train", "moe_route_ms.train", "gmm_roofline.train",
          "moe_load_max_over_mean.train", "rope_ms.train"}
BEFORE = ["gpt3-1.3b.train-2k", "gpt2-small.train-1k",
          "jamba2-3b-l14.train-4k", "laguna-xs2-l5-e64.train-8k",
          "zaya1-8b-l5-e8.train-32k", "qwen3-next-80b-l4-e64.train-16k",
          "ouro-2.6b-l8.train-4k"]
OURO = BEFORE[-1]
# what other configurations' kernels and mixers alone can report
OTHERS_OWN = ["mfu.train", "flash_roofline.train",
              "flash_window_roofline.train", "mfu_laguna.train",
              "flash_cca_roofline.train", "cca_mix_ms.train",
              "mfu_zaya.train", "ssm_scan_ms.train", "gdn_mixer_ms.train",
              "flash_d256_roofline.train", "mfu_qwen3next.train",
              "mfu_ouro.train", "ut_loop_ms.train",
              "flash_ouro_roofline.train"]


def test_the_cell_joins_the_shared_metrics_and_brings_its_own():
    """The lists by their beginnings and by membership, so that a cell or
    a metric a later PR appends by files alone leaves this passing."""
    spec = Spec(REPO)
    doc = spec.doc
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    # the driver refuses a `why` over 200 characters before any run
    assert all(1 <= len(e["why"]) <= 200 and e["why"].isprintable()
               for e in (cell, config))
    assert all(w["chips"] == 1 for w in doc["workloads"])
    assert [w["name"] for w in doc["workloads"][:8]] == BEFORE + [CELL]
    assert [c["name"] for c in doc["configs"][:8]][-1] == CONFIG
    mine = {m["name"] for m in doc["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine >= JOINED | set(OWN) | set(pinned.READERS)
    assert next(m for m in doc["end_to_end"]
                if m["name"] == "train_tok_s_chip")["workloads"][:8] == \
        BEFORE + [CELL]
    layer = next(m["layer"] for m in doc["per_layer"]
                 if m["name"] == "step_device_ms.train")
    kernel_layer = next(m["layer"] for m in doc["per_layer"]
                        if m["name"] == "flash_roofline.train")
    for m in doc["per_layer"]:
        if m["name"] in OWN:
            kernels = m["name"].endswith("_roofline.train")
            assert m == {"name": m["name"], "unit": m["unit"],
                         "better": m["better"], "source": "device_trace",
                         "layer": kernel_layer if kernels else layer,
                         "moves": "train_tok_s_chip", "workloads": [CELL]}
            assert (m["unit"], m["better"]) == (
                ("ms", "lower") if m["name"] == "mla_latent_ms.train"
                else ("%", "higher"))
            assert callable(spec.module("layer_metrics", m["name"]).read)
        elif m["name"] in JOINED | set(pinned.READERS):
            # the cell came after those the list had
            i = m["workloads"].index(CELL)
            assert i >= 1 and m["workloads"][i - 1] in BEFORE
        elif m["name"] in OTHERS_OWN:
            assert CELL not in m["workloads"], m["name"]
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index(OWN[0])
    assert names[first:first + 3] == OWN
    assert names[first - 1] == "flash_ouro_roofline.train"
    # the experts' shared entries: three cells and this one
    for name in ("moe_ffn_ms.train", "moe_route_ms.train",
                 "gmm_roofline.train", "moe_load_max_over_mean.train"):
        entry = next(m for m in doc["per_layer"] if m["name"] == name)
        assert entry["workloads"][:4] == BEFORE[3:6] + [CELL]


def test_the_ouro_configurations_entry_stands_as_it_was():
    """Every assertion of `test_ouro.py::
    test_the_file_holds_the_published_row_but_for_what_reduced_names` but
    the one that its entry is the list's last: this PR's followed
    (`tests/conftest.py:_PINNED`). Held by membership."""
    import test_ouro as ouro
    spec = Spec(REPO)
    cfg = spec.data("configs", ouro.CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == ouro.CONFIG)
    assert cfg["source"] == entry["source"] == ouro.URL
    assert entry["reduced"] == cfg["reduced"] == list(ouro.CUT)
    assert entry["file"] == "benchmarks/configs/ouro-2.6b-l8.json"
    for key, value in ouro.PUBLISHED.items():
        assert cfg[key] == ouro.CUT.get(key, value), key
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["num_hidden_layers"] >= 4 and cfg["total_ut_steps"] == 4
    assert {"layer", "loop", "exit_gate", "loss", "attention", "weights",
            "learning_rate"} <= set(cfg["assumed"])
    assert "six pipeline stages of eight layers" in cfg["deployment"]
    assert "first stage" in cfg["deployment"]
    assert cfg["training"]["exit_entropy_beta"] == 0.05
    assert cfg["seeded_draws"]["residual_output"] == pytest.approx(
        0.02 / (2 * 192) ** 0.5)
    assert spec.doc["configs"][6] == entry      # where it came to stand


# -- counted costs -------------------------------------------------------------
def test_flops_against_a_count_by_hand():
    from harness import deepseek_v2_flops as flops
    cfg = Spec(REPO).data("configs", CONFIG)
    parts = flops.parts_per_token(cfg, 32768)
    attention = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert parts["attention_projections"] == 6.0 * 7 * attention
    assert parts["attention"] == 6.0 * 7 * 16 * (192 + 128) * 32769 / 2
    assert parts["dense_ffn"] == 6.0 * 3 * 2048 * 10944
    assert parts["router_and_shared"] == 6.0 * 6 * (
        2048 * 64 + 3 * 2048 * 2816)
    assert flops.held_per_token(cfg) == 0.75
    assert parts["routed_experts"] == 6.0 * 6 * 0.75 * 3 * 2048 * 1408
    assert parts["head"] == 6.0 * 2048 * 12800
    total = flops.train_flops_per_token(cfg, 32768)
    assert total == sum(parts.values())
    # the issue's arithmetic: a sparse layer 75M operations of matrices
    # a token forward and 168M of causal attention; 5.5 GFLOP a token,
    # 181 TFLOP a step
    matrices = (parts["attention_projections"] / 7
                + (parts["router_and_shared"]
                   + parts["routed_experts"]) / 6) / 3
    assert round(matrices / 1e6) == 75
    assert round(parts["attention"] / 7 / 3 / 1e6) == 168
    assert round(total / 1e9, 1) == 5.5
    assert round(total * 32768 / 1e12) == 181


def test_the_kernels_cost_against_a_count_by_hand():
    costs = Spec(REPO).module("kernel_costs", "flash_mla")
    pairs = 16 * 32768 * 32769 / 2
    ops_f, bytes_f = costs.cost("fwd", 1, 32768, 16, 128, 64, 128)
    assert ops_f == 2 * pairs * (192 + 128)
    q, k, v = (32768 * 16 * 192 * 2, 32768 * (16 * 128 + 64) * 2,
               32768 * 16 * 128 * 2)
    lse = 16 * 32768 * 4
    assert bytes_f == q + k + 2 * v + lse
    ops_b, bytes_b = costs.cost("bwd", 1, 32768, 16, 128, 64, 128)
    assert ops_b == 2 * pairs * (2 * 192 + 2 * 128)
    assert bytes_b == 2 * q + 2 * k + 4 * v + 2 * lse
    # compute-bound on a v5e thirty times over
    assert ops_f / 197e12 > 30 * bytes_f / 819e9
    path = "jit(step)/x/model/layers/3/attn/flash_mla_bwd_transpose"
    assert costs.classify(path) == ("bwd", 3)
    assert costs.classify("a/layers/0/attn/flash_mla_fwd") == ("fwd", 0)
    assert costs.classify("a/layers/0/attn/flash_fwd") is None
    assert costs.classify("a/attn/flash_mla_fwd") is None
    with pytest.raises(KeyError):
        costs.cost("dq", 1, 128, 2, 128, 64, 128)
