"""The six readers of set-up (`setup_import_s.train`,
`setup_build_s.train`, `step_trace_s.train`, `step_first_run_s.train`,
`setup_other_programs_s.train`, `setup_named_share.train`) on a run made
by hand, with a record made by hand: what lies between the run's two
ends counts, each instant once; a CPU rehearsal and a program without
the record read nothing. And `tools/setup_table.py`'s table of the same
record."""
import importlib.util
import os
import sys
import types

import pytest

from harness.spec import BENCH_DIR, REPO, Spec
from paddle_tpu.observability import perf

CELLS = ["gpt3-1.3b.train-2k", "gpt2-small.train-1k",
         "jamba2-3b-l14.train-4k"]
READERS = {"setup_import_s.train": ("s", "lower"),
           "setup_build_s.train": ("s", "lower"),
           "step_trace_s.train": ("s", "lower"),
           "step_first_run_s.train": ("s", "lower"),
           "setup_other_programs_s.train": ("s", "lower"),
           "setup_named_share.train": ("%", "higher")}
T0 = 1000.0         # the process's start on the hand-made clock


def phase(stretches, s=None, n=1, parent=None, **counts):
    t0 = min((a for a, _b in stretches), default=T0)
    t1 = max((b for _a, b in stretches), default=T0)
    return dict(s=sum(b - a for a, b in stretches) if s is None else s,
                n=n, t0=t0, t1=t1, parent=parent, stretches=stretches,
                **counts)


def row(t1, seconds, kind, name, family=None, phase=None, step=None):
    return perf.ProgramRow(t1, kind, name, seconds, family, phase, step)


# The process starts at 1000 and the window at 1040: 40 s of set-up.
#   1002..1005 import; 1006..1010 the model (initialisers 1007..1009, a
#   program of theirs 1007..1008); 1011..1013 the harness's weights (the
#   program `build`, no phase); 1013..1013.5 the criterion; 1014..1016
#   TrainStep.__init__ (the accumulators inside); 1017..1027 the lowering
#   (trace 1017..1023), 1027..1030 the backend (a load inside),
#   1030..1031 the first run; 1033..1035 `leaf_norms`; then, outside the
#   ends: a layer built at 1045, a program at 1050, and an import of
#   another life at 990.
SETUP = {
    "import": phase([(1002.0, 1005.0)]),
    "build.model": phase([(1006.0, 1010.0), (1013.0, 1013.5),
                          (1045.0, 1046.0)], n=40),
    "build.params": phase([], s=2.0, n=12, parent="build.model",
                          params=12, bytes=4096),
    "build.train_step": phase([(1014.0, 1016.0)]),
    "build.optimizer": phase([], s=1.5, n=12, parent="build.train_step"),
    "train_step.lower": phase([(1017.0, 1027.0)]),
    "train_step.trace": phase([], s=6.0, parent="train_step.lower"),
    "train_step.backend": phase([(1027.0, 1030.0)]),
    "train_step.first_run": phase([(1030.0, 1031.0)]),
    "t.before": phase([(990.0, 995.0)]),
}
ROWS = [
    row(995.0, 5.0, "backend", "before_the_process"),
    row(1008.0, 1.0, "backend", "_normal", phase="build.params"),
    row(1011.5, 0.5, "trace", "build"),
    row(1012.0, 0.5, "lower", "build"),
    row(1012.8, 0.6, "load", "build"),
    row(1013.0, 1.0, "backend", "build"),
    row(1023.0, 6.0, "trace", "step", "train_step", "train_step.trace", 0),
    row(1027.0, 4.0, "lower", "step", "train_step", "train_step.lower", 0),
    row(1029.5, 2.0, "load", "step", "train_step", "train_step.backend", 0),
    row(1030.0, 3.0, "backend", "step", "train_step", "train_step.backend",
        0),
    row(1033.5, 0.5, "trace", "<lambda>"),
    row(1035.0, 1.5, "backend", "<lambda>"),
    row(1050.0, 2.0, "backend", "in_the_window", "train_step", None, 7),
]
RECORD = {"compiles": 1, "lower_s": 10.0, "trace_s": 6.0, "backend_s": 3.0,
          "first_run_s": 1.0, "outcome": "disk_hit",
          "trace_by_scope": {"model/layers/*/mamba": 3.0, "ssm_scan_fwd": 1.5,
                             "optimizer": 0.5}}


@pytest.fixture
def run(monkeypatch):
    """A traced run of a program that keeps the record above."""
    monkeypatch.setattr(perf, "setup_record", lambda: SETUP)
    monkeypatch.setattr(perf, "program_log", lambda: {
        "rows": ROWS, "totals": {"traced_inside": {"n": 9, "s": 0.25}}})
    monkeypatch.setattr(perf, "compile_record",
                        lambda family: dict(RECORD)
                        if family == "train_step" else None)
    return types.SimpleNamespace(spec=Spec(REPO), scoped=object(),
                                 t_process=T0, window={"t0": 1040.0})


def read(run, name):
    return run.spec.module("layer_metrics", name).read(run)


@pytest.mark.parametrize("name,want", [
    ("setup_import_s.train", 3.0),
    # the model 4, the criterion 0.5, TrainStep.__init__ 2: the layer
    # built inside the window is no set-up
    ("setup_build_s.train", 6.5),
    ("step_trace_s.train", 6.0),
    ("step_first_run_s.train", 1.0),
    # _normal 1, build 1011..1013 once (its load lies inside its backend),
    # leaf_norms' lambda 1033..1035; not the step's, nothing outside
    ("setup_other_programs_s.train", 5.0),
    # import 3 + model 4 (its program inside it) + build 2 + the criterion
    # 0.5 + TrainStep 2 + the first call 14 + the lambda 2 = 27.5 of 40
    ("setup_named_share.train", 68.75),
])
def test_a_reader_counts_what_lies_between_the_ends_once(run, name, want):
    assert read(run, name) == pytest.approx(want)


def test_the_ends_cut_what_straddles_them(run):
    run.t_process, run.window["t0"] = 1003.0, 1008.5
    assert read(run, "setup_import_s.train") == pytest.approx(2.0)
    assert read(run, "setup_build_s.train") == pytest.approx(2.5)
    assert read(run, "setup_other_programs_s.train") == pytest.approx(1.0)
    # import 1003..1005 and the model 1006..1008.5, of 5.5 s
    assert read(run, "setup_named_share.train") == pytest.approx(
        100 * 4.5 / 5.5)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_cpu_rehearsal_reads_nothing(run, name):
    run.scoped = None
    assert read(run, name) is None


@pytest.mark.parametrize("name", [
    "setup_import_s.train", "setup_build_s.train",
    "setup_other_programs_s.train", "setup_named_share.train",
    "step_trace_s.train"])
def test_a_program_without_the_record_reads_nothing(run, monkeypatch, name):
    """The parent commit: no `setup_record`, no `trace_s` (it has kept
    `first_run_s` since PR 25, which `step_first_run_s.train` reads)."""
    monkeypatch.delattr(perf, "setup_record")
    monkeypatch.setattr(perf, "compile_record", lambda family: {
        "compiles": 1, "lower_s": 10.0, "backend_s": 3.0,
        "first_run_s": 1.0, "outcome": "compile"})
    assert read(run, name) is None
    assert read(run, "step_first_run_s.train") == 1.0


def test_the_union_counts_each_instant_once():
    share = Spec(REPO).module("layer_metrics", "setup_named_share.train")
    assert share.union_s([]) == 0.0
    assert share.union_s([(3.0, 4.0), (0.0, 2.0), (1.0, 2.5),
                          (0.5, 1.0)]) == pytest.approx(3.5)
    assert share.union_s([(0.0, 10.0), (2.0, 3.0), (9.0, 11.0)]) == \
        pytest.approx(11.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_entries_of_benchmark_json(name):
    doc = Spec(REPO).doc
    entry, = [m for m in doc["per_layer"] if m["name"] == name]
    unit, better = READERS[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["better"]) == (unit, better)
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "setup_s" and entry["workloads"] == CELLS
    assert 1 <= len(entry["layer"]) <= 200 and "\n" not in entry["layer"]
    # the last six, appended in the issue's order; a reader each
    assert [m["name"] for m in doc["per_layer"][-6:]] == [
        "setup_import_s.train", "setup_build_s.train", "step_trace_s.train",
        "step_first_run_s.train", "setup_other_programs_s.train",
        "setup_named_share.train"]
    assert os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics",
                                       name + ".py"))
    assert any(m["name"] == "setup_s" for m in doc["end_to_end"])


@pytest.fixture(scope="module")
def table():
    tools = os.path.join(BENCH_DIR, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)       # its `_common`, as when it is run
    spec = importlib.util.spec_from_file_location(
        "bench_setup_table", os.path.join(BENCH_DIR, "tools",
                                          "setup_table.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_table_lays_the_phases_and_the_gaps_on_the_clock(table):
    lines = table.clock_lines(SETUP, ROWS, [(1001.0, "a mark")], T0, 1040.0)
    assert [(at, s, text.split(":")[0]) for at, s, text in lines] == [
        (0.0, 2.0, "(gap) no program"),
        (1.0, 0.0, "-- a mark"),
        (2.0, 3.0, "import"),
        (5.0, 1.0, "(gap) no program"),
        (6.0, 4.0, "build.model"),
        (10.0, 3.0, "(gap) 3 events of 1 programs, 2.00 s"),
        (13.0, 0.5, "build.model"),
        (13.5, 0.5, "(gap) no program"),
        (14.0, 2.0, "build.train_step"),
        (16.0, 1.0, "(gap) no program"),
        (17.0, 10.0, "train_step.lower"),
        (27.0, 3.0, "train_step.backend"),
        (30.0, 1.0, "train_step.first_run"),
        (31.0, 9.0, "(gap) 2 events of 1 programs, 2.00 s"),
    ]
    assert lines[5][2].endswith(": build 2.00")


def test_the_table_gives_a_phase_its_self_seconds(table):
    by_name = {line[0]: line for line in table.phase_lines(SETUP)}
    assert by_name["build.model"][1:5] == (40, 5.5, 3.5, "-")
    assert by_name["build.params"][3:] == (2.0, "build.model",
                                           {"params": 12, "bytes": 4096})
    assert by_name["train_step.lower"][2:4] == (10.0, 4.0)
    assert list(by_name)[0] == "t.before"       # by their first entry


def test_the_table_says_whose_each_program_is(table):
    rows = [r for r in ROWS if T0 <= r.t <= 1040.0]
    lines = table.program_lines(rows, "train_step")
    assert [(name, steps, seconds) for name, steps, _k, seconds in lines] == [
        ("step", True, 13.0), ("build", False, 2.0), ("<lambda>", False, 2.0),
        ("_normal", False, 1.0)]
    assert lines[0][2]["load"] == (1, 2.0)      # shown, not summed twice
    text = table.render("a-cell", 7, "TPU v5 lite", SETUP, RECORD,
                        {"rows": ROWS,
                         "totals": {"traced_inside": {"n": 9, "s": 0.25}}},
                        [], T0, 1040.0)
    assert "lower_s 10.000 = trace_s 6.000 + lowering 4.000" in text
    assert "backend_s 3.000 (disk_hit); first_run_s 1.000" in text
    assert "3 scopes, 5.000 s of the trace's 6.000" in text
    assert "    3.000  model/layers/*/mamba" in text
    assert "3 other than the step's, 5.000 s; the step's 13.000 s" in text
    assert "in_the_window" not in text and "before_the_process" not in text
