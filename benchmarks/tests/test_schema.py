"""`BENCHMARK.json` against the contract, and every name against a file."""
import json
import os
import re

import pytest

from harness.spec import BENCH_DIR, REPO, Spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module",
                params=["as committed", "with the tests' serving cells"])
def spec(request, tmp_path_factory):
    """The committed benchmark, and the tiny copy the other tests run,
    which adds the serving cells of `tests/data/serving.json`: the same
    rules hold for both."""
    if request.param == "as committed":
        return Spec(REPO)
    import tiny
    return Spec(tiny.make_tiny_repo(str(tmp_path_factory.mktemp("tiny"))))


def test_top_level_keys_and_sizes(spec):
    d = spec.doc
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    assert d["paths"] == ["benchmarks"] and 1 <= d["run_seconds"] <= 51
    assert all(w.startswith("benchmarks/") or "/" not in w
               for w in d["command"])
    cells = len(d["workloads"])
    assert (2 + 14 * 24) * (d["run_seconds"] + 60) + 24 * 2 * 90 + 1200 \
        <= 43200, "run_seconds does not fit a full check of 24 cells"
    assert sum(w["chips"] == 4 for w in d["workloads"]) <= max(1, cells // 4)


def test_names_units_and_keys(spec):
    d = spec.doc
    names = []
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        names.append(w["name"])
    for m in d["end_to_end"]:
        # setup_s is every cell's, those of later PRs too: it lists none
        assert set(m) == {"name", "unit", "better", "bound", "source"} | (
            set() if m["name"] == "setup_s" else {"workloads"})
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in d["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for m in d["end_to_end"] + d["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in d["end_to_end"]]


def test_every_metric_lists_cells_that_report_what_it_moves(spec):
    d = spec.doc
    cells = {w["name"] for w in d["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in d["end_to_end"]}
    for m in d["end_to_end"] + d["per_layer"]:
        if m["name"] != "setup_s":
            assert m["workloads"] and set(m["workloads"]) <= cells, m["name"]
    for m in d["per_layer"]:
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
    for c in cells:
        assert c in e2e["setup_s"]
        assert any(c in ws for n, ws in e2e.items() if n != "setup_s")
        assert spec.metrics("per_layer", c)
    used = {w["config"] for w in d["workloads"]}
    assert used == {c["name"] for c in d["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in d["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_everything_resolves_to_a_file_by_name(spec):
    d = spec.doc
    for c in d["configs"]:
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        cfg = spec.data("configs", c["name"])
        assert cfg["reduced"] == c["reduced"] and cfg["source"]
        ref = spec.module("reference", c["name"])
        assert ref.Model and ref.Trainer and ref.param_specs(cfg)
    for w in d["workloads"]:
        cell = spec.data("cells", w["name"])
        assert {k: cell[k] for k in ("config", "traffic", "chips", "why")} \
            == {k: w[k] for k in ("config", "traffic", "chips", "why")}
        mix = spec.data("traffic", w["traffic"])
        assert callable(spec.module("drivers", mix["driver"]).run)
        for m in spec.metrics("per_layer", w["name"]):
            assert callable(spec.module("layer_metrics", m["name"]).read)
    for root, _dirs, files in os.walk(BENCH_DIR):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (root, f)


def test_last_line_of_a_rehearsal_has_the_contracts_keys(rehearse):
    line = rehearse("gpt3-1.3b.serve-chat-flood", seconds=0.6, trace=1)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "compared"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
            "window_s"} <= set(line["device"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    line = rehearse("gpt3-1.3b.train-2k", seconds=0.3, trace=0)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}


def test_no_chip_no_result(capsys):
    from conftest import load_run
    with pytest.raises(SystemExit) as e:
        load_run().main(["--workload", "gpt3-1.3b.train-2k", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert not capsys.readouterr().out.strip()
