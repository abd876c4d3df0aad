"""`rope_ms.train` against the traces recorded on the chip that the
Laguna and ZAYA1 trace tests use (`data/laguna.xplane.pb`,
`data/zaya.xplane.pb`: the composite's operations carry the `rope` scope
there, as the kernel `rope_rotate` does since PR 38), and its entry in
`BENCHMARK.json`.

The entry is the first after the six that `test_setup_metrics.py` pins
as the file's last, and it joins the two cells whose sets of metrics
`test_zaya.py` pins: none of the three files can be edited by the PR
that adds it (the driver refuses a PR that edits a benchmark file), so
`tests/conftest.py` marks those assertions and this file holds what
else they asserted (PERF.md section 7)."""
import os
import sys
import types

import pytest

import test_setup_metrics as pinned
from harness import trace_scopes
from harness.spec import BENCH_DIR, REPO, Spec
from harness.trace_reduce import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))

NAME = "rope_ms.train"
LAGUNA, ZAYA = "laguna-xs2-l5-e64.train-8k", "zaya1-8b-l5-e8.train-32k"
SHARED = {"step_device_ms.train", "device_idle.train", "head_loss_ms.train",
          "optimizer_unfused_ms.train", "recompute_ms.train",
          "host_step_ms.train", "step_lower_s.train", "step_compile_s.train",
          "moe_ffn_ms.train", "moe_route_ms.train", "gmm_roofline.train",
          "moe_load_max_over_mean.train", NAME}
# the six set-up readers and their cells, as the pinned test has them
SETUP_CELLS, SETUP_READERS = pinned.CELLS, pinned.READERS


def _run(trace, recorder=None):
    """What `run.py` hands a reader, for a recorded session."""
    path = os.path.join(HERE, "data", trace)
    mix = {"batch": 1, "seq": 1}
    if recorder:
        recorded = __import__(recorder)
        mix = {"batch": recorded.ROWS, "seq": recorded.SEQ}
    return types.SimpleNamespace(
        spec=Spec(REPO), cfg={}, mix=mix,
        trace_summary=Trace.from_file(path),
        device={"kind": "TPU v5 lite"},
        tracer=types.SimpleNamespace(xplane=lambda: path),
        window={"tokens_per_step": mix["batch"] * mix["seq"]})


def read(run, name=NAME):
    return run.spec.module("layer_metrics", name).read(run)


@pytest.mark.parametrize("trace,recorder,root,layers", [
    ("laguna.xplane.pb", "record_laguna_trace",
     "lagunaforcausallm/laguna/layers", 3),
    ("zaya.xplane.pb", "record_zaya_trace",
     "zayaforcausallm/zaya/layers", 2)])
def test_rope_time_is_everything_under_that_scope(trace, recorder, root,
                                                  layers):
    run = _run(trace, recorder)
    table = trace_scopes.of(run).by_scope("jit_step")
    mine = {(c, p): t for (c, p), t in table.items()
            if "rope" in c.split("/")}
    assert read(run) == pytest.approx(1e3 * sum(mine.values()))
    # every attention layer's q and k, each way: forward, again, back
    for layer in range(layers):
        assert {p for (c, p) in mine
                if c.startswith(f"{root}/{layer}/attn/rope")} >= {
            "forward", "backward", "recompute"}, layer
    assert all("/attn/rope" in c for c, _p in mine)
    # a part of the step and not its kernels': the flash calls beside it
    assert 0 < read(run) < read(run, "step_device_ms.train")
    assert not any("flash" in c or "proj" in c.rsplit("/", 1)[-1]
                   for c, _p in mine)


@pytest.mark.parametrize("other", ["scoped.xplane.pb", "jamba.xplane.pb",
                                   "small.xplane.pb"])
def test_a_program_without_the_scope_reads_nothing(other):
    """The GPT traces of PRs 24 and 25 and the Jamba trace of PR 27: no
    layer of theirs turns q and k. The reader returns nothing and does
    not raise (a CPU rehearsal, which has no TPU plane to read, is
    `test_zaya.py`'s and `test_laguna.py`'s traced rehearsal)."""
    assert read(_run(other)) is None


def test_the_entry_of_benchmark_json():
    spec = Spec(REPO)
    doc = spec.doc
    entry, = [m for m in doc["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace",
        "layer": next(m["layer"] for m in doc["per_layer"]
                      if m["name"] == "cca_mix_ms.train"),
        "moves": "train_tok_s_chip", "workloads": [LAGUNA, ZAYA]}
    for cell in (LAGUNA, ZAYA):
        assert NAME in {m["name"] for m in spec.metrics("per_layer", cell)}
    for cell in SETUP_CELLS:
        assert NAME not in {m["name"]
                            for m in spec.metrics("per_layer", cell)}
    assert callable(spec.module("layer_metrics", NAME).read)


@pytest.mark.parametrize("cell,config,traffic,own,others", [
    (LAGUNA, "laguna-xs2-l5-e64", "pretrain-8k",
     {"flash_window_roofline.train", "mfu_laguna.train"},
     {"mfu.train", "flash_roofline.train"}),
    (ZAYA, "zaya1-8b-l5-e8", "pretrain-32k",
     {"cca_mix_ms.train", "flash_cca_roofline.train", "mfu_zaya.train"},
     {"mfu.train", "flash_roofline.train", "flash_window_roofline.train",
      "mfu_laguna.train"})])
def test_the_two_cells_report_what_they_did_and_the_rotary(
        cell, config, traffic, own, others):
    """Every assertion of `test_zaya.py`'s two tests of the cells' sets of
    metrics (and of `test_laguna.py`'s, which the second of them stands
    for), with the one metric that joined the sets."""
    doc = Spec(REPO).doc
    entry = next(w for w in doc["workloads"] if w["name"] == cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        config, traffic, 1)
    assert all(w["chips"] == 1 for w in doc["workloads"])
    # at least: a later PR adds cells by files alone and cannot edit this
    assert len(doc["workloads"]) >= 5
    mine = {m["name"] for m in doc["per_layer"]
            if cell in m.get("workloads", [])}
    assert mine == SHARED | own
    for m in doc["per_layer"]:
        if m["name"] in others:
            assert cell not in m["workloads"]
        if m["name"] in own:
            assert m["workloads"] == [cell]


@pytest.mark.parametrize("name", sorted(SETUP_READERS))
def test_the_set_up_entries_stand_as_they_were(name):
    """Every assertion of `test_setup_metrics.py::
    test_the_entries_of_benchmark_json`; for its claim to the list's last
    six places: the six in the issue's order, one after the other, and
    after them this PR's entry (a new entry goes to the end of its list:
    one put in the middle reads to the driver as a change to what was
    there)."""
    doc = Spec(REPO).doc
    entry, = [m for m in doc["per_layer"] if m["name"] == name]
    unit, better = SETUP_READERS[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["better"]) == (unit, better)
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "setup_s" and entry["workloads"] == SETUP_CELLS
    assert 1 <= len(entry["layer"]) <= 200 and "\n" not in entry["layer"]
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index("setup_import_s.train")
    assert names[first:first + 7] == [
        "setup_import_s.train", "setup_build_s.train", "step_trace_s.train",
        "step_first_run_s.train", "setup_other_programs_s.train",
        "setup_named_share.train", NAME]
    assert os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics",
                                       name + ".py"))
    assert any(m["name"] == "setup_s" for m in doc["end_to_end"])
