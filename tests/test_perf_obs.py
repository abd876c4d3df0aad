"""Performance observability (ISSUE 8): the one cost-model reader,
executable flops/bytes gauges per compile family, roofline accounting
against device peaks (honest no-series on unknown devices), the eager
backward dispatch-gap profiler, the perf ledger, and the disabled-mode
zero-overhead guard extended over all of it."""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.observability import metrics, perf, tracing


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends disabled with empty series/ring and
    no device-peak override (the registry and override are
    process-global)."""
    obs.disable()
    obs.reset()
    perf.set_device_peaks()
    yield
    obs.disable()
    obs.reset()
    perf.set_device_peaks()


def _series(name):
    return obs.snapshot()[name]["series"]


@pytest.fixture(scope="module")
def tiny_gpt():
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import gpt_tiny
    pt.seed(0)
    return GPTForCausalLM(gpt_tiny())


def _tiny_compiled():
    import jax
    import jax.numpy as jnp

    def f(a, b):
        return jnp.tanh(a @ b).sum()

    a = jnp.ones((8, 8), jnp.float32)
    return jax.jit(f).lower(a, a).compile(), (a, a)


# ---------------------------------------------------------------------------
# the one cost-model reader
# ---------------------------------------------------------------------------
class TestCostModelReader:
    def test_reads_flops_and_bytes(self):
        compiled, _ = _tiny_compiled()
        cm = perf.read_cost_model(compiled)
        assert cm is not None
        assert cm.flops > 0                  # 8x8x8 matmul at least
        assert cm.bytes_accessed > 0
        assert cm.bytes_argument > 0
        d = cm.as_dict()
        assert set(d) == {"flops", "bytes_accessed", "bytes_output",
                          "bytes_argument", "bytes_temp"}
        assert json.dumps(d)                 # ledger-serializable

    def test_unreadable_executable_is_none_not_zero(self):
        assert perf.read_cost_model(object()) is None


# ---------------------------------------------------------------------------
# CompileTimed: compile telemetry + cost model + degradation contract
# ---------------------------------------------------------------------------
class TestCompileTimed:
    def test_first_call_records_family_once(self):
        import jax
        import jax.numpy as jnp
        obs.enable()
        fn = perf.CompileTimed(jax.jit(lambda a: (a * 2).sum()),
                               "t_fam_ct")
        x = jnp.ones((4,), jnp.float32)
        out1 = fn(x)
        out2 = fn(x)
        assert float(out1) == float(out2) == 8.0
        comp = _series("paddle_tpu_compile_total")
        assert comp[("t_fam_ct", "compile")] == 1      # once, not per call
        assert fn.expected is not None and fn.expected.flops > 0
        fl = _series("paddle_tpu_executable_flops")
        assert fl[("t_fam_ct",)] == fn.expected.flops
        by = _series("paddle_tpu_executable_bytes")
        for kind in ("accessed", "output", "temp", "argument"):
            assert ("t_fam_ct", kind) in by
        assert by[("t_fam_ct", "accessed")] > 0

    def test_new_signature_falls_back_to_jit(self):
        import jax
        import jax.numpy as jnp
        obs.enable()
        fn = perf.CompileTimed(jax.jit(lambda a: a.sum()), "t_fam_sig")
        assert float(fn(jnp.ones((4,), jnp.float32))) == 4.0
        # AOT executables are monomorphic: a new shape must revert the
        # shim to the polymorphic jit function, not raise
        assert fn.expected is not None
        assert float(fn(jnp.ones((6,), jnp.float32))) == 6.0
        assert fn.fn is fn.jit_fn
        # the recorded cost model described the FIRST signature only —
        # after the revert, roofline reads must go silent, not stale
        assert fn.expected is None
        assert float(fn(jnp.ones((4,), jnp.float32))) == 4.0

    def test_expected_readable_even_when_disabled(self):
        import jax
        import jax.numpy as jnp
        fn = perf.CompileTimed(jax.jit(lambda a: a * 3), "t_fam_off")
        fn(jnp.ones((4,), jnp.float32))
        # tools (profile_engine columns) read .expected regardless of
        # metric recording; the registry saw nothing
        assert fn.expected is not None and fn.expected.flops > 0
        assert _series("paddle_tpu_compile_total").get(
            ("t_fam_off", "compile"), 0) == 0


# ---------------------------------------------------------------------------
# roofline accounting
# ---------------------------------------------------------------------------
class TestRoofline:
    def test_unknown_device_publishes_no_series(self):
        obs.enable()
        assert perf.device_peaks() is None   # the CPU test box
        perf.observe_roofline("t_fam_cpu", 0.01,
                              perf.CostModel(flops=1e6,
                                             bytes_accessed=1e6))
        roof = _series("paddle_tpu_roofline_utilization")
        assert not any(v for k, v in roof.items()
                       if k[0] == "t_fam_cpu")
        # the achieved record still accumulates (the ledger does not
        # need a peak to report absolute rates)
        rec = perf.family_records()["t_fam_cpu"]
        assert rec["achieved_bytes_per_s"] == pytest.approx(1e8)
        assert rec["utilization_hbm"] is None

    def test_peaks_are_keyed_by_the_device_kind_jax_reports(self):
        """The installed runtime calls a v5e chip "TPU v5 lite" (the
        described topology says so). The table answers for exactly the
        kinds it lists: an unknown kind has no peak, and "TPU v5" (v5p)
        does not answer for "TPU v5 lite"."""
        import types
        from jax.experimental import topologies
        v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
        assert perf.lookup(v5e, perf.PEAK_BF16_FLOPS) == 197e12
        assert perf.lookup(v5e, perf.HBM_BYTES_PER_SEC) == 819e9
        assert perf.device_peaks(v5e) == (197e12, 819e9)
        assert perf.interconnect_peaks(v5e)["ici"] == 2.0e11
        assert perf.lookup(types.SimpleNamespace(device_kind="TPU v5"),
                           perf.PEAK_BF16_FLOPS) == 459e12
        for kind in ("TPU v9 imaginary", "cpu", "tpu v5 lite", None):
            dev = types.SimpleNamespace(device_kind=kind)
            assert perf.lookup(dev, perf.PEAK_BF16_FLOPS) is None
            assert perf.device_peaks(dev) is None
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"cannot describe a v5e topology: {e}")
        assert perf.device_peaks(topo.devices[0]) == (197e12, 819e9)

    def test_bench_refuses_a_device_without_peaks(self, monkeypatch):
        """bench.py used to assume v5e peaks for a device it did not
        know; now that is an error, and only a run asked for by name as
        a CPU smoke gets a not-a-number instead."""
        import math
        import types
        import bench
        unknown = types.SimpleNamespace(device_kind="TPU v9 imaginary")
        with pytest.raises(RuntimeError, match="PEAK_BF16_FLOPS"):
            bench.peak_flops(unknown)
        with pytest.raises(RuntimeError, match="HBM_BYTES_PER_SEC"):
            bench.hbm_bw(unknown)
        v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
        assert bench.peak_flops(v5e) == 197e12
        assert bench.hbm_bw(v5e) == 819e9
        monkeypatch.setattr(bench, "_CPU_SMOKE", True)
        assert math.isnan(bench.peak_flops(unknown))
        assert bench._finite_or_none(
            {"mfu": float("nan"), "n": [1, float("inf")], "s": "x"}) == \
            {"mfu": None, "n": [1, None], "s": "x"}

    def test_pinned_peaks_give_exact_utilization(self):
        obs.enable()
        perf.set_device_peaks(1e12, 1e11)
        perf.observe_roofline(
            "t_fam_pin", 0.01,
            perf.CostModel(flops=5e9, bytes_accessed=2e8))
        roof = _series("paddle_tpu_roofline_utilization")
        assert roof[("t_fam_pin", "flops")] == pytest.approx(5e11 / 1e12)
        assert roof[("t_fam_pin", "hbm")] == pytest.approx(2e10 / 1e11)
        rec = perf.family_records()["t_fam_pin"]
        assert rec["utilization_flops"] == pytest.approx(0.5)
        assert rec["utilization_hbm"] == pytest.approx(0.2)

    def test_disabled_records_nothing(self):
        perf.observe_roofline("t_fam_dis", 0.01,
                              perf.CostModel(flops=1e6,
                                             bytes_accessed=1e6))
        assert "t_fam_dis" not in perf.family_records()

    def test_window_resets_with_obs_reset(self):
        obs.enable()
        perf.observe_roofline("t_fam_win", 0.01,
                              perf.CostModel(flops=1.0,
                                             bytes_accessed=1.0))
        assert "t_fam_win" in perf.family_records()
        obs.reset()
        assert perf.family_records() == {}


# ---------------------------------------------------------------------------
# the wired paths: engine launches, fused optimizer, TrainStep,
# eager backward
# ---------------------------------------------------------------------------
def _one_train_and_eager_step():
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import SGD, AdamW
    lin = pt.nn.Linear(8, 8)
    opt = pt.optimizer.AdamW(learning_rate=1e-3,
                             parameters=lin.parameters())
    x = pt.to_tensor(np.ones((2, 8), np.float32))
    (lin(x) ** 2).mean().backward()          # eager backward: gaps
    opt.step()                               # fused family
    opt.clear_grad()
    lin2 = pt.nn.Linear(8, 8)
    step = TrainStep(lin2, SGD(learning_rate=1e-3,
                               parameters=lin2.parameters()),
                     lambda m, a: (m(a) ** 2).mean())
    xa = np.ones((4, 8), np.float32)
    for _ in range(4):                       # >=1 steady-state sample
        step(xa)


class TestWiredFamilies:
    def test_engine_train_and_optimizer_families_report(self, tiny_gpt):
        from paddle_tpu.inference import LLMEngine
        obs.enable()
        perf.set_device_peaks(1e12, 1e11)    # CPU box: pin peaks
        rng = np.random.default_rng(3)
        eng = LLMEngine(tiny_gpt, max_batch=2, block_size=16,
                        decode_chunk=4, prompt_quantum=16,
                        max_model_len=64)
        res = eng.generate(
            [rng.integers(0, 1024, (n,)).astype(np.int32)
             for n in (5, 9, 13)], max_new_tokens=8)
        assert all(r.ok for r in res)
        _one_train_and_eager_step()

        live = {fam for (fam, _out), v in
                _series("paddle_tpu_compile_total").items() if v}
        assert {"engine_ragged", "engine_decode", "optimizer_fused",
                "train_step"} <= live
        fl = _series("paddle_tpu_executable_flops")
        fl_fams = {fam for (fam,), v in fl.items() if v}
        # one gauge row per live family, no orphan families
        assert fl_fams == live
        by = _series("paddle_tpu_executable_bytes")
        for fam in live:
            assert by[(fam, "accessed")] > 0
            for kind in ("output", "temp", "argument"):
                assert (fam, kind) in by
        # roofline: engine launches are blocking-timed, the train loop
        # samples steady-state inter-step periods; the async-dispatched
        # fused optimizer honestly publishes none
        roof = _series("paddle_tpu_roofline_utilization")
        roof_fams = {fam for (fam, _b), v in roof.items() if v}
        assert {"engine_ragged", "engine_decode",
                "train_step"} <= roof_fams
        assert "optimizer_fused" not in roof_fams
        for fam in ("engine_ragged", "engine_decode", "train_step"):
            assert roof[(fam, "hbm")] > 0
            assert roof[(fam, "flops")] > 0
        recs = perf.family_records()
        assert recs["optimizer_fused"]["achieved_bytes_per_s"] is None
        assert recs["engine_decode"]["achieved_bytes_per_s"] > 0
        assert json.dumps(recs)              # ledger-serializable

    def test_eager_backward_records_dispatch_gaps(self):
        from paddle_tpu.autograd import dispatch_queue as dq
        obs.enable()
        lin1, lin2 = pt.nn.Linear(8, 8), pt.nn.Linear(8, 8)
        x = pt.to_tensor(np.ones((4, 8), np.float32))
        # per_node mode: one gap per inter-node hop (the batched engine
        # collapses the whole chain into one dispatch — see below)
        with dq.backward_dispatch_mode("per_node"):
            for _ in range(3):
                (lin2(pt.ops.tanh(lin1(x))) ** 2).mean().backward()
        gap = _series("paddle_tpu_dispatch_gap_seconds")[()]
        # >= 2 inter-node gaps per backward over the 4-op chain
        assert gap["count"] >= 6
        assert gap["sum"] > 0
        ops = _series("paddle_tpu_dispatch_gap_op_seconds_total")
        assert ops                           # attributed by op type
        assert any(v > 0 for v in ops.values())
        assert pytest.approx(gap["sum"]) == sum(ops.values())

    def test_batched_backward_pins_batch_size_histogram(self):
        # ISSUE 10: the batched engine's run lengths are a pinned
        # series — a 5-node single-consumer chain is ONE fused
        # dispatch (batch size 5, zero inter-dispatch gaps)
        from paddle_tpu.autograd import dispatch_queue as dq
        obs.enable()
        lin1, lin2 = pt.nn.Linear(8, 8), pt.nn.Linear(8, 8)
        x = pt.to_tensor(np.ones((4, 8), np.float32))
        with dq.backward_dispatch_mode("batched"):
            for _ in range(3):
                (lin2(pt.ops.tanh(lin1(x))) ** 2).mean().backward()
        batch = _series("paddle_tpu_dispatch_batch_size")[()]
        assert batch["count"] == 3           # one dispatch per backward
        assert batch["max"] == 5
        assert batch["sum"] == 15            # every node dispatched
        gap = _series("paddle_tpu_dispatch_gap_seconds")[()]
        assert gap["count"] == 0

    def test_disabled_backward_records_nothing(self):
        lin = pt.nn.Linear(4, 4)
        x = pt.to_tensor(np.ones((2, 4), np.float32))
        (lin(x) ** 2).mean().backward()
        assert _series(
            "paddle_tpu_dispatch_gap_seconds")[()]["count"] == 0
        assert _series(
            "paddle_tpu_dispatch_batch_size")[()]["count"] == 0


# ---------------------------------------------------------------------------
# disabled-mode zero-overhead guard, extended over the perf paths
# ---------------------------------------------------------------------------
class TestDisabledOverhead:
    def test_no_allocation_growth_when_disabled(self):
        import tracemalloc
        assert not obs.enabled()
        cm = perf.CostModel(flops=1e6, bytes_accessed=1e6)
        for _ in range(16):                  # warm lazy state
            perf.observe_roofline("t_ov_perf", 0.01, cm)
        tracemalloc.start()
        base = tracemalloc.get_traced_memory()[0]
        for _ in range(5000):
            # the roofline recorder and the tape's per-node guard are
            # both a single module-flag check when off
            perf.observe_roofline("t_ov_perf", 0.01, cm)
            if metrics._ENABLED:
                pytest.fail("enabled")
        grown = tracemalloc.get_traced_memory()[0] - base
        tracemalloc.stop()
        assert grown < 2048, f"disabled-mode perf ops leaked {grown}B"
        assert perf.family_records() == {}
        assert tracing.events() == []


# ---------------------------------------------------------------------------
# perf ledger: bench appends, tools/perf_ledger.py attributes
# ---------------------------------------------------------------------------
def _ledger_record(rev, config, fams, device="cpu", mode=None,
                   gap_ms_per_step=None):
    rec = {"rev": rev, "config": config, "ts": 1.0,
           "device": device, "metric": "m", "value": 1.0,
           "vs_baseline": 1.0,
           "families": {
               f: {"runs": 3, "compiles": 1, "seconds": 0.01,
                   "expected": None,
                   "achieved_flops_per_s": None,
                   "achieved_bytes_per_s": bps,
                   "utilization_hbm": None,
                   "utilization_flops": None}
               for f, bps in fams.items()}}
    if mode is not None:
        rec["mode"] = mode
    if gap_ms_per_step is not None:
        rec["dispatch_gap"] = {"steps": 20, "count": 80,
                               "total_ms": gap_ms_per_step * 20,
                               "ms_per_step": gap_ms_per_step}
    return rec


def _perf_ledger():
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import perf_ledger
    finally:
        sys.path.pop(0)
    return perf_ledger


class TestPerfLedger:
    def _write(self, path, records):
        with open(path, "w", encoding="utf-8") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")

    def test_same_revision_ledger_is_self_consistent(self, tmp_path):
        pl = _perf_ledger()
        p = str(tmp_path / "ledger.jsonl")
        self._write(p, [
            _ledger_record("rev_a", "decode", {"engine_decode": 1e9}),
            _ledger_record("rev_a", "decode", {"engine_decode": 0.5e9}),
        ])
        assert pl.main(["--ledger", p, "--check"]) == 0
        records, bad = pl.load(p)
        assert bad == 0
        v = pl.check(records, tol=0.2)
        # same-rev delta reported but NOT failed: run-to-run noise is
        # the gate's business, attribution is this tool's
        fam = v["configs"]["decode"]["families"]["engine_decode"]
        assert fam["ratio_vs_history"] == pytest.approx(0.5)
        assert v["pass"]

    def test_cross_revision_regression_names_the_family(self, tmp_path):
        pl = _perf_ledger()
        p = str(tmp_path / "ledger.jsonl")
        self._write(p, [
            _ledger_record("rev_a", "decode",
                           {"engine_decode": 1e9, "engine_ragged": 2e9}),
            _ledger_record("rev_b", "decode",
                           {"engine_decode": 0.5e9,
                            "engine_ragged": 1.95e9}),
        ])
        assert pl.main(["--ledger", p, "--check"]) == 1
        records, _ = pl.load(p)
        v = pl.check(records, tol=0.2)
        fams = v["configs"]["decode"]["families"]
        assert fams["engine_decode"]["regressed"]       # the culprit
        assert not fams["engine_ragged"]["regressed"]   # within tol
        assert fams["engine_decode"]["baseline_rev"] == "rev_a"

    def test_disappeared_family_fails(self, tmp_path):
        pl = _perf_ledger()
        p = str(tmp_path / "ledger.jsonl")
        self._write(p, [
            _ledger_record("rev_a", "decode",
                           {"engine_decode": 1e9, "engine_ragged": 2e9}),
            _ledger_record("rev_b", "decode", {"engine_decode": 1e9}),
        ])
        records, _ = pl.load(p)
        v = pl.check(records, tol=0.2)
        assert not v["pass"]
        assert v["configs"]["decode"]["missing_families"] == \
            ["engine_ragged"]

    def test_other_device_records_are_not_baselines(self, tmp_path):
        pl = _perf_ledger()
        p = str(tmp_path / "ledger.jsonl")
        # a v5e record must not make the CPU smoke run of a different
        # revision read as a 100x per-family regression
        self._write(p, [
            _ledger_record("rev_a", "decode", {"engine_decode": 100e9},
                           device="TPU v5 lite"),
            _ledger_record("rev_b", "decode", {"engine_decode": 1e9},
                           device="cpu"),
        ])
        records, _ = pl.load(p)
        v = pl.check(records, tol=0.2)
        assert v["pass"]
        fam = v["configs"]["decode"]["families"]["engine_decode"]
        assert fam["ratio_vs_history"] is None    # no same-device prior

    def test_modes_baseline_independently(self, tmp_path):
        # ISSUE 10: batched and per_node dispatch records are separate
        # baseline groups — per_node's (larger) gap must not read as a
        # regression baseline for batched, nor vice versa
        pl = _perf_ledger()
        p = str(tmp_path / "ledger.jsonl")
        self._write(p, [
            _ledger_record("rev_a", "dispatch", {}, mode="per_node",
                           gap_ms_per_step=0.2),
            _ledger_record("rev_a", "dispatch", {}, mode="batched",
                           gap_ms_per_step=0.01),
            _ledger_record("rev_b", "dispatch", {}, mode="per_node",
                           gap_ms_per_step=0.21),
            _ledger_record("rev_b", "dispatch", {}, mode="batched",
                           gap_ms_per_step=0.012),
        ])
        records, _ = pl.load(p)
        v = pl.check(records, tol=0.2)
        assert v["pass"]
        assert set(v["configs"]) == {"dispatch[per_node]",
                                     "dispatch[batched]"}
        g = v["configs"]["dispatch[batched]"]["dispatch_gap"]
        assert g["baseline_rev"] == "rev_a"
        assert not g["regressed"]

    def test_whole_graph_mode_and_graph_cache_ride_the_ledger(
            self, tmp_path):
        # ISSUE 13: whole_graph records baseline per (config, mode)
        # like the PR 10 modes, and their graph-cache counts are
        # echoed in the verdict and the trajectory (report-only)
        pl = _perf_ledger()
        p = str(tmp_path / "ledger.jsonl")
        recs = [
            _ledger_record("rev_a", "dispatch", {}, mode="whole_graph",
                           gap_ms_per_step=0.0),
            _ledger_record("rev_b", "dispatch", {}, mode="whole_graph",
                           gap_ms_per_step=0.004),
        ]
        recs[-1]["graph_cache"] = {"hit": 20, "miss": 1}
        self._write(p, recs)
        records, _ = pl.load(p)
        v = pl.check(records, tol=0.2)
        assert v["pass"]            # 0.004 is under the absolute floor
        out = v["configs"]["dispatch[whole_graph]"]
        assert out["graph_cache"] == {"hit": 20, "miss": 1}
        traj = pl.trajectory(records)
        assert "(graph cache)" in traj
        assert "hit=20 miss=1 bypass=0" in traj

    def test_dispatch_gap_regression_fails_per_mode(self, tmp_path):
        pl = _perf_ledger()
        p = str(tmp_path / "ledger.jsonl")
        self._write(p, [
            _ledger_record("rev_a", "dispatch", {}, mode="batched",
                           gap_ms_per_step=0.01),
            _ledger_record("rev_b", "dispatch", {}, mode="batched",
                           gap_ms_per_step=0.05),   # 5x the gap
        ])
        records, _ = pl.load(p)
        v = pl.check(records, tol=0.2)
        assert not v["pass"]
        g = v["configs"]["dispatch[batched]"]["dispatch_gap"]
        assert g["regressed"]
        assert g["ratio_vs_history"] == pytest.approx(5.0)
        # same-revision gap deltas report, never fail (box noise)
        self._write(p, [
            _ledger_record("rev_a", "dispatch", {}, mode="batched",
                           gap_ms_per_step=0.01),
            _ledger_record("rev_a", "dispatch", {}, mode="batched",
                           gap_ms_per_step=0.05),
        ])
        records, _ = pl.load(p)
        assert pl.check(records, tol=0.2)["pass"]

    def test_zero_gap_baseline_has_finite_sensitivity(self, tmp_path):
        # the routine batched result is ms_per_step=0.0 (one fused
        # dispatch per backward, zero gaps): timer jitter above it
        # must NOT read as a regression — the absolute floor applies
        pl = _perf_ledger()
        p = str(tmp_path / "ledger.jsonl")
        self._write(p, [
            _ledger_record("rev_a", "dispatch", {}, mode="batched",
                           gap_ms_per_step=0.0),
            _ledger_record("rev_b", "dispatch", {}, mode="batched",
                           gap_ms_per_step=0.004),   # < floor
        ])
        records, _ = pl.load(p)
        assert pl.check(records, tol=0.2)["pass"]
        # but a real gap reappearing over a zero baseline still fails
        self._write(p, [
            _ledger_record("rev_a", "dispatch", {}, mode="batched",
                           gap_ms_per_step=0.0),
            _ledger_record("rev_b", "dispatch", {}, mode="batched",
                           gap_ms_per_step=0.1),
        ])
        records, _ = pl.load(p)
        v = pl.check(records, tol=0.2)
        assert not v["pass"]
        assert v["configs"]["dispatch[batched]"][
            "dispatch_gap"]["regressed"]

    def test_autotune_sweeps_render_in_trajectory(self, tmp_path):
        pl = _perf_ledger()
        p = str(tmp_path / "ledger.jsonl")
        rec = _ledger_record("rev_a", "gpt2s", {"train_step": 1e9})
        rec["autotune_sweeps"] = [{
            "key": ["fwd", 2048], "device": "TPU_v5e",
            "candidates": {"(256, 1024)": 0.002, "(512, 512)": 0.001},
            "winner": [512, 512], "bw_window": [233e9, 314e9],
            "window_validated": True, "persisted": True}]
        self._write(p, [rec])
        records, _ = pl.load(p)
        table = pl.trajectory(records)
        assert "autotune" in table and "fwd|2048" in table
        assert "validated=True" in table
        # sweeps never affect the regression verdict
        assert pl.check(records, tol=0.2)["pass"]

    def test_missing_ledger_is_loud(self, tmp_path):
        pl = _perf_ledger()
        assert pl.main(["--ledger", str(tmp_path / "none.jsonl"),
                        "--check"]) == 2

    def test_trajectory_renders(self, tmp_path):
        pl = _perf_ledger()
        p = str(tmp_path / "ledger.jsonl")
        self._write(p, [_ledger_record("rev_a", "decode",
                                       {"engine_decode": 1e9})])
        records, _ = pl.load(p)
        table = pl.trajectory(records)
        assert "engine_decode" in table and "rev_a" in table


# ---------------------------------------------------------------------------
# obs_top roofline panel (render-tested like the spec-accept line)
# ---------------------------------------------------------------------------
class TestObsTopRooflinePanel:
    def _obs_top(self):
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        try:
            import obs_top
        finally:
            sys.path.pop(0)
        return obs_top

    def test_renders_utilization_and_gap(self):
        obs_top = self._obs_top()
        obs.enable()
        perf.set_device_peaks(1e12, 1e11)
        perf.observe_roofline(
            "engine_decode", 0.01,
            perf.CostModel(flops=5e9, bytes_accessed=2e8))
        perf.note_dispatch_gap(120e-6, "linear")
        perf.note_dispatch_gap(80e-6, "tanh")
        frame = obs_top.render(json.loads(obs.to_json()))
        assert "== roofline ==" in frame
        assert "engine_decode" in frame
        assert "hbm=" in frame and "flops=" in frame
        assert "dispatch gap" in frame and "n=2" in frame

    def test_gap_percentiles_between_frames(self):
        obs_top = self._obs_top()
        obs.enable()
        perf.note_dispatch_gap(100e-6, "linear")
        prev = json.loads(obs.to_json())
        for _ in range(3):
            perf.note_dispatch_gap(200e-6, "linear")
        doc = json.loads(obs.to_json())
        frame = obs_top.render(doc, prev, dt=1.0)
        # the between-frames window holds 3 gaps, not the cumulative 4
        assert "n=3" in frame

    def test_renders_graph_cache_line(self):
        obs_top = self._obs_top()
        obs.enable()
        for _ in range(9):
            perf.note_graph_cache("hit")
        perf.note_graph_cache("miss")
        frame = obs_top.render(json.loads(obs.to_json()))
        assert "graph cache" in frame
        assert "90.0%" in frame
        assert "9 hit / 1 miss / 0 bypass" in frame
