"""The Ouro configuration and cell at a size a CPU test can hold: the
timed path's own objects against the plain reference on seeded weights
(two AdamW steps, every leaf, the eight numbers of the aux), the one
command end to end, the comparison's verdicts with `pass_gap` (every
control of `tools/limits_ouro.py`, a program whose loop runs a pass too
few, a state returned unchanged), the configuration's file against the
published row and its size check, the entries of `BENCHMARK.json`, and
the counted operations against a count by hand. The program's model
against the reference leaf by leaf is `tests/test_ouro_model.py`; the
readers against a trace recorded on the chip are in
`test_ouro_trace.py`."""
import json
import os
import sys

import numpy as np
import pytest

import test_setup_metrics as pinned
from conftest import load_run
from harness.spec import BENCH_DIR, REPO, Spec

sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))

CONFIG, CELL = "ouro-2.6b-l8", "ouro-2.6b-l8.train-4k"
URL = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
# the tiny size's own: 64 tokens a step and weights of 0.15
TINY_LIMITS = {"loss": 1e-4, "grad_norm_worst_leaf": 0.02,
               "change_norm_median_leaf": 0.005, "pass_gap": 1e-3}
# smaller than `tiny.TINY_MODEL` leaves it: 3 layers of 4 heads of 16
# run four times, and draws under which every part is felt
SMALL = dict(num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=4, intermediate_size=128,
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
    program in float32 (in bfloat16 at 64 tokens no limit lies between
    the program and the controls; the bfloat16 program is held to its
    limits at the timed size, on the chip: `tools/limits_ouro.py`)."""
    cfg = tiny_spec.data("configs", CONFIG)
    cfg.update(SMALL)
    cfg["training"]["amp"] = {"level": "O0", "dtype": "float32"}
    cfg["training"]["optimizer"]["moment_dtype"] = "float32"
    return (cfg, tiny_spec.module("reference", CONFIG),
            tiny_spec.module("drivers", "ouro_train_window"),
            dict(tiny_spec.data("traffic", "pretrain-4k-x2"), seq=40))


@pytest.fixture(scope="module")
def sides(small):
    """(the program's first steps, the reference's) on seed 1, made once
    for the tests that read them."""
    cfg, ref, tw, mix = small
    n = ref.CHECK_STEPS
    prog = tw.first_steps(tw.build_step(cfg, 1, ref), cfg, mix, 1, ref, n)
    return prog, tw.reference_steps(cfg, mix, 1, ref, n)


# -- the program against the reference ------------------------------------
def test_two_adamw_steps_match_the_reference(small, sides):
    """The timed path's own objects in float32: `TrainStep` on the
    program against the reference's `Trainer`, every leaf's first
    gradient norm and change after two steps, and the aux."""
    from drivers.train_window import leaf_gaps
    cfg, ref, tw, _mix = small
    prog, plain = sides
    np.testing.assert_allclose(prog["losses"], plain["losses"], rtol=5e-6)
    assert max(leaf_gaps(prog["grad_norms"], plain["grad_norms"])) < 1e-4
    assert max(leaf_gaps(prog["change_norms"], plain["change_norms"])) < 1e-4
    assert prog["aux"].shape == plain["aux"].shape == (2, 4)
    assert max(tw.pass_gaps(prog["aux"], plain["aux"])) < 1e-5
    np.testing.assert_allclose(plain["aux"][1].sum(), 1.0, rtol=1e-6)
    assert len(prog["grad_norms"]) == len(ref.param_specs(cfg)) == 38
    got = tw.compare(prog, plain, TINY_LIMITS)
    assert set(got) == {"loss_step1", "loss_step2", "grad_norm_worst_leaf",
                        "change_norm_median_leaf", "pass_gap"}
    assert all(v["value"] <= v["limit"] for v in got.values()), got


def test_the_seeded_draws_are_the_files(small):
    cfg, ref, _tw, _mix = small
    plain = ref.Model(cfg, 9)
    by_name = dict(zip((n for n, _s, _i in plain.specs), plain.params))
    norms = [np.asarray(v) for k, v in by_name.items() if "norm" in k]
    assert len(norms) == 4 * 3 + 1
    assert all(abs(w.mean() - 1) < 0.06 and 0.05 < w.std() < 0.2
               for w in norms)
    assert float(np.abs(by_name["exit_gate.bias"]).max()) == 0.0
    assert 0.8 < float(np.std(by_name["model.embed_tokens.weight"])) < 1.2
    # the gate's logit spreads (weights of 0.15 on 64 unit channels: a
    # deviation of 1.2) and no lambda_t is degenerate
    ids = np.random.default_rng(0).integers(0, 500, (2, 40))
    _logits, gates = plain.logits(ids)
    lam = 1 / (1 + np.exp(-np.asarray(gates)))
    assert 0.01 < lam.min() and lam.max() < 0.99 and lam.std() > 0.1


# -- the one command ---------------------------------------------------------
def test_the_cell_runs_end_to_end_and_is_correct(tmp_path, capsys):
    """`conftest.rehearse`, keeping the notes' line beside the result's.
    bfloat16 at 64 tokens: the limits here only hold the control flow."""
    import tiny
    repo = tiny.make_tiny_repo(str(tmp_path / "repo"))
    capsys.readouterr()
    load_run().main(["--workload", CELL, "--seed", "7", "--seconds", "0.5",
                     "--trace", "0"], repo=repo, require_chip=False)
    out = [json.loads(ln) for ln in
           capsys.readouterr().out.strip().splitlines() if ln[:1] == "{"]
    line, notes = out[-1], out[-2]["notes"]
    assert line["correct"] is True and line["failed"] == 0, line
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    # both steps' losses behind the cell's one limit, both sides' noted
    assert set(line["compared"]) == {"loss_step1", "loss_step2",
                                     "grad_norm_worst_leaf",
                                     "change_norm_median_leaf", "pass_gap"}
    assert len(notes["program_losses"]) == len(
        notes["reference_losses"]) == 2
    assert notes["paths"]["ut_loop"] == "scan, 4 x 8 layers"
    assert notes["paths"]["head_loss"] == "fused, chunks 1, rows 256"
    assert len(notes["pass_gaps"]) == 8
    for key in ("program_first", "reference_first"):
        assert np.asarray(notes["exit"][key]).shape == (2, 4)
    assert len(notes["exit"]["window_first_last"]) == 2
    assert notes["steps"] == line["attempted"] >= 1


def test_a_traced_rehearsal_reads_what_a_cpu_can_and_does_not_raise(
        rehearse):
    line = rehearse(CELL, seconds=0.5, trace=1)
    assert line["correct"] is True
    # no TPU plane in a CPU trace: every device reader returns nothing
    assert not {"mfu_ouro.train", "ut_loop_ms.train",
                "ut_grad_sum_ms.train", "exit_mix_ms.train",
                "rope_ms.train", "step_device_ms.train"} & set(
                    line["metrics"])


# -- the comparison's verdicts ---------------------------------------------
@pytest.mark.parametrize("control", ["fp8", "final_norm", "post_norms",
                                     "gate_gradient", "entropy", "passes"])
def test_a_control_reads_above_the_program(small, sides, control):
    """Every control of `tools/limits_ouro.py` fails one limit at least,
    where the program passes all: the reference in fp8; the final norm
    left out between passes; N2 and N4 left out; p_t held constant under
    the gradient; beta 0; three passes for four."""
    import limits_ouro
    cfg, ref, tw, mix = small
    assert control in limits_ouro.CONTROLS
    assert set(limits_ouro.CONTROLS) == {"fp8"} | set(limits_ouro.PARTS)
    prog, exact = sides
    sound = tw.compare(prog, exact, TINY_LIMITS)
    assert all(v["value"] <= v["limit"] for v in sound.values()), sound
    n = ref.CHECK_STEPS
    if control == "fp8":
        broken = tw.reference_steps(cfg, mix, 1, ref, n, rnd=ref.fp8)
    else:
        broken = tw.reference_steps(cfg, mix, 1, ref, n, parts=(control,))
    got = tw.compare(broken, exact, TINY_LIMITS)
    failed = {k for k, v in got.items() if not v["value"] <= v["limit"]}
    assert failed, got
    if control in ("gate_gradient", "entropy"):
        # the forward is the same: only a gradient tells
        assert got["pass_gap"]["value"] == 0.0
        assert "grad_norm_worst_leaf" in failed
    if control in ("final_norm", "post_norms", "passes"):
        assert "pass_gap" in failed
    if control == "passes":
        assert broken["aux"].shape == (2, 3)
        assert max(tw.pass_gaps(broken["aux"], exact["aux"])) >= 1.0


def test_pass_gap_sees_a_wrong_pass_that_the_loss_hides():
    """Two passes swapped: the weighted sum is the same, the eight
    numbers are not."""
    tw = Spec(REPO).module("drivers", "ouro_train_window")
    ref_aux = np.array([[6.0, 5.0, 4.0, 3.0], [0.25, 0.25, 0.25, 0.25]])
    swapped = ref_aux[:, [1, 0, 2, 3]]
    assert np.isclose((ref_aux[0] * ref_aux[1]).sum(),
                      (swapped[0] * swapped[1]).sum())
    gaps = tw.pass_gaps(swapped, ref_aux)
    assert len(gaps) == 8 and max(gaps) == pytest.approx(0.2)
    assert tw.pass_gaps(ref_aux, ref_aux) == [0.0] * 8
    side = {"losses": [1.0], "grad_norms": [1.0, 2.0],
            "change_norms": [1.0, 2.0]}
    got = tw.compare(dict(side, aux=swapped), dict(side, aux=ref_aux),
                     {"grad_norm_worst_leaf": 0.01,
                      "change_norm_median_leaf": 0.001, "pass_gap": 1e-3})
    assert got["pass_gap"] == {"value": pytest.approx(0.2), "limit": 1e-3}
    # a cell that gives the loss no limit leaves it out (the Jamba
    # cell does; this one gives it: test_the_cells_file_states...)
    assert "loss_step1" not in got


def test_a_program_that_runs_a_pass_too_few_is_not_correct(small, sides,
                                                           monkeypatch):
    """The same verdict on the timed path itself."""
    from harness import runlib
    from paddle_tpu.models import ouro
    cfg, ref, tw, mix = small
    real = ouro.OuroConfig.from_dict.__func__

    def from_dict(cls, d, **kw):
        c = real(cls, d, **kw)
        c.total_ut_steps -= 1
        return c

    monkeypatch.setattr(ouro.OuroConfig, "from_dict", classmethod(from_dict))
    prog = tw.first_steps(tw.build_step(cfg, 1, ref), cfg, mix, 1, ref,
                          ref.CHECK_STEPS)
    got = tw.compare(prog, sides[1], TINY_LIMITS)
    assert got["pass_gap"]["value"] >= 1.0
    assert runlib.judge(got) is False


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
                    if row["name"] == "Ouro-2.6B")


PUBLISHED = {   # the row's `config`
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
CUT = {"num_hidden_layers": 8}


def test_the_file_holds_the_published_row_but_for_what_reduced_names():
    spec = Spec(REPO)
    cfg = spec.data("configs", CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] == URL
    assert entry["reduced"] == cfg["reduced"] == list(CUT)
    assert entry["file"] == "benchmarks/configs/ouro-2.6b-l8.json"
    for key, value in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, value), key
    assert cfg["published"] == {"num_hidden_layers": 48}
    # the guide's floors: a period is one layer; four of them and more
    assert cfg["num_hidden_layers"] >= 4 and cfg["total_ut_steps"] == 4
    assert {"layer", "loop", "exit_gate", "loss", "attention", "weights",
            "learning_rate"} <= set(cfg["assumed"])
    assert "six pipeline stages of eight layers" in cfg["deployment"]
    assert "first stage" in cfg["deployment"]
    assert cfg["training"]["exit_entropy_beta"] == 0.05
    assert cfg["seeded_draws"]["residual_output"] == pytest.approx(
        0.02 / (2 * 192) ** 0.5)
    assert spec.doc["configs"][-1] == entry     # the list's last


def test_the_file_against_the_catalogs_row():
    row = _published_row()
    assert row["config"] == PUBLISHED
    assert row["source_url"] == URL


@pytest.mark.parametrize("layers,count", [(None, 612438017),
                                          (48, 2667974657)])
def test_the_size_check_by_the_references_own_parameter_list(layers, count):
    """Held: 8 layers and the rest; whole: the published 2.6B."""
    ref = Spec(REPO).module("reference", CONFIG)
    cfg = Spec(REPO).data("configs", CONFIG)
    assert ref.n_params(cfg, layers) == count
    assert f"{count:,}" in cfg["size_check"]
    a_layer = ref.n_params(cfg, 1) - ref.n_params(cfg, 0)
    assert a_layer == 16777216 + 34603008 + 8192 == 51388416
    assert ref.n_params(cfg, 0) == 2 * 100663296 + 2048 + 2049


def test_the_program_reads_the_cut_from_the_file():
    from paddle_tpu.models.ouro import OuroConfig
    c = OuroConfig.from_dict(Spec(REPO).data("configs", CONFIG))
    assert (c.num_hidden_layers, c.total_ut_steps, c.residual_depth) == (
        8, 4, 192)
    assert (c.vocab_size, c.hidden_size, c.intermediate_size) == (
        49152, 2048, 5632)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.rope_theta) == (16, 16, 128, 1000000)


def test_the_cells_file_states_its_limits_and_their_readings():
    spec = Spec(REPO)
    cell = spec.data("cells", CELL)
    assert cell["limits"] == {"loss": 3.5e-4, "grad_norm_worst_leaf": 0.04,
                              "change_norm_median_leaf": 0.07,
                              "pass_gap": 0.025}
    for word in ("fp8", "final norm", "N2", "p_t", "beta 0", "three passes",
                 "my chip runs, PR 41", "one limit over both steps",
                 "26 seeds", "over 6 seeds", "ONE seed"):
        assert word in cell["readings"], word
    mix = spec.data("traffic", "pretrain-4k-x2")
    assert (mix["batch"], mix["seq"], mix["driver"]) == (
        2, 4096, "ouro_train_window")
    assert (mix["trace_after_s"], mix["trace_for_s"]) == (3.0, 6.0)


# -- the entries of BENCHMARK.json ----------------------------------------------
OWN = ["mfu_ouro.train", "ut_loop_ms.train", "ut_grad_sum_ms.train",
       "exit_mix_ms.train", "flash_ouro_roofline.train"]
JOINED = {"step_device_ms.train", "device_idle.train", "head_loss_ms.train",
          "optimizer_unfused_ms.train", "recompute_ms.train",
          "host_step_ms.train", "step_lower_s.train", "step_compile_s.train",
          "rope_ms.train"}
BEFORE = ["gpt3-1.3b.train-2k", "gpt2-small.train-1k",
          "jamba2-3b-l14.train-4k", "laguna-xs2-l5-e64.train-8k",
          "zaya1-8b-l5-e8.train-32k", "qwen3-next-80b-l4-e64.train-16k"]
QWEN = BEFORE[-1]
# what other configurations' kernels and mixers alone can report
OTHERS_OWN = ["mfu.train", "flash_roofline.train",
              "flash_window_roofline.train", "mfu_laguna.train",
              "flash_cca_roofline.train", "cca_mix_ms.train",
              "mfu_zaya.train", "ssm_scan_ms.train"]
QWEN_OWN = ["gdn_mixer_ms.train", "gdn_state_ms.train",
            "gdn_state_roofline.train", "flash_d256_roofline.train",
            "mfu_qwen3next.train", "gdn_prepare_ms.train"]


def test_the_cell_joins_the_shared_metrics_and_brings_its_own():
    spec = Spec(REPO)
    doc = spec.doc
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-4k-x2", 1)
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    # the driver refuses a `why` over 200 characters before any run
    assert all(1 <= len(e["why"]) <= 200 and e["why"].isprintable()
               for e in (cell, config))
    assert all(w["chips"] == 1 for w in doc["workloads"])
    # at least: a later PR adds cells by files alone and cannot edit this
    assert len(doc["workloads"]) >= 7
    assert [w["name"] for w in doc["workloads"][:7]] == BEFORE + [CELL]
    # at least, here too: a later PR may list the cell under a new metric
    mine = {m["name"] for m in doc["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine >= JOINED | set(OWN) | set(pinned.READERS)
    assert next(m for m in doc["end_to_end"]
                if m["name"] == "train_tok_s_chip")["workloads"][:7] == \
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
                ("%", "higher") if kernels or m["name"] == "mfu_ouro.train"
                else ("ms", "lower"))
            assert callable(spec.module("layer_metrics", m["name"]).read)
        elif m["name"] in JOINED | set(pinned.READERS):
            i = m["workloads"].index(CELL)
            assert m["workloads"][i - 1] == QWEN
        elif m["name"] in OTHERS_OWN + QWEN_OWN:
            assert CELL not in m["workloads"], m["name"]
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index(OWN[0])
    assert names[first:first + 5] == OWN and names[first - 1] == \
        "gdn_prepare_ms.train"


LAGUNA, ZAYA = "laguna-xs2-l5-e64.train-8k", "zaya1-8b-l5-e8.train-32k"


def test_the_qwen3_next_cell_reports_what_it_did():
    """Every assertion of `test_qwen3next.py::
    test_the_cell_joins_the_shared_metrics_and_brings_its_own` but the
    one that its six entries are the list's last: others followed. The
    lists are held by their beginnings and by membership, so that a cell
    or a metric appended by files alone leaves this test, and the two
    below, passing (`tests/conftest.py`'s `_PINNED` need not grow)."""
    spec = Spec(REPO)
    doc = spec.doc
    cell = next(w for w in doc["workloads"] if w["name"] == QWEN)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-l4-e64", "pretrain-16k", 1)
    mine = {m["name"] for m in doc["per_layer"]
            if QWEN in m.get("workloads", [])}
    assert mine >= JOINED | set(QWEN_OWN) | set(pinned.READERS) | {
        "moe_ffn_ms.train", "moe_route_ms.train", "gmm_roofline.train",
        "moe_load_max_over_mean.train"}
    assert QWEN in next(m for m in doc["end_to_end"]
                        if m["name"] == "train_tok_s_chip")["workloads"]
    for m in doc["per_layer"]:
        if m["name"] in QWEN_OWN:
            assert m["workloads"] == [QWEN] and m["unit"] in ("ms", "%")
            assert m["moves"] == "train_tok_s_chip"
            assert callable(spec.module("layer_metrics", m["name"]).read)
        if m["name"] in OTHERS_OWN:
            assert QWEN not in m["workloads"]
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index(QWEN_OWN[0])
    assert names[first:first + 6] == QWEN_OWN


def test_the_rotarys_entry_stands_as_it_was_with_this_cell_at_its_end():
    """Every assertion of `test_qwen3next.py`'s test of the same name,
    with the cell that joined the list; the list by its beginning."""
    spec = Spec(REPO)
    doc = spec.doc
    entry, = [m for m in doc["per_layer"] if m["name"] == "rope_ms.train"]
    cells = [LAGUNA, ZAYA, QWEN, CELL]
    assert {**entry, "workloads": entry["workloads"][:4]} == {
        "name": "rope_ms.train", "unit": "ms", "better": "lower",
        "source": "device_trace",
        "layer": next(m["layer"] for m in doc["per_layer"]
                      if m["name"] == "cca_mix_ms.train"),
        "moves": "train_tok_s_chip", "workloads": cells}
    for cell in cells:
        assert "rope_ms.train" in {
            m["name"] for m in spec.metrics("per_layer", cell)}
    for cell in pinned.CELLS:
        assert "rope_ms.train" not in {
            m["name"] for m in spec.metrics("per_layer", cell)}
    assert callable(spec.module("layer_metrics", "rope_ms.train").read)


@pytest.mark.parametrize("name", sorted(pinned.READERS))
def test_the_set_up_entries_stand_as_they_were_with_this_cell_at_their_end(
        name):
    """Every assertion of `test_qwen3next.py`'s test of the same name,
    with the cell that joined each list; each list by its beginning."""
    doc = Spec(REPO).doc
    entry, = [m for m in doc["per_layer"] if m["name"] == name]
    unit, better = pinned.READERS[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["better"]) == (unit, better)
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "setup_s"
    cells = pinned.CELLS + [QWEN, CELL]
    assert entry["workloads"][:len(cells)] == cells
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


# -- counted costs -------------------------------------------------------------
def test_flops_against_a_count_by_hand():
    from harness import ouro_flops as flops
    cfg = Spec(REPO).data("configs", CONFIG)
    parts = flops.parts_per_token(cfg, 4096)
    matrices = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert parts["layer_matrices"] == 6.0 * 32 * matrices
    assert parts["attention"] == 12.0 * 32 * 16 * 128 * 4097 / 2
    assert parts["heads"] == 6.0 * 4 * 2048 * 49152
    assert parts["exit_gates"] == 6.0 * 4 * 2048
    total = flops.train_flops_per_token(cfg, 4096)
    assert total == sum(parts.values())
    # the issue's arithmetic: a layer application 308.3 MFLOP a token in
    # its matrices and 50.3 in its causal product; 13.9 GFLOP a token,
    # 17% of it the four heads; 3.4% in the whole model
    assert round(parts["layer_matrices"] / 32 / 1e6, 1) == 308.3
    assert round(parts["attention"] / 32 / 1e6, 1) == 50.3
    assert round(total / 1e9, 1) == 13.9
    assert round(100 * parts["heads"] / total) == 17
    whole = flops.parts_per_token(dict(cfg, num_hidden_layers=48), 4096)
    assert round(100 * whole["heads"] / sum(whole.values()), 1) == 3.4
    assert os.path.isfile(os.path.join(BENCH_DIR, "harness",
                                       "ouro_flops.py"))
