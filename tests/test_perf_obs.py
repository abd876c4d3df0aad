"""Performance observability (ISSUE 8): the one cost-model reader,
executable flops/bytes gauges per compile family, roofline accounting
against device peaks (honest no-series on unknown devices), the eager
backward dispatch-gap profiler, and the disabled-mode zero-overhead
guard extended over all of it."""
import json

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
        assert json.dumps(d)                 # a plain record

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
        # .expected is there regardless of metric recording; the
        # registry saw nothing
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

    def test_pinned_peaks_give_exact_utilization(self):
        obs.enable()
        perf.set_device_peaks(1e12, 1e11)
        perf.observe_roofline(
            "t_fam_pin", 0.01,
            perf.CostModel(flops=5e9, bytes_accessed=2e8))
        roof = _series("paddle_tpu_roofline_utilization")
        assert roof[("t_fam_pin", "flops")] == pytest.approx(5e11 / 1e12)
        assert roof[("t_fam_pin", "hbm")] == pytest.approx(2e10 / 1e11)

    def test_disabled_records_nothing(self):
        perf.set_device_peaks(1e12, 1e11)    # known peaks, metrics off
        perf.observe_roofline("t_fam_dis", 0.01,
                              perf.CostModel(flops=1e6,
                                             bytes_accessed=1e6))
        roof = _series("paddle_tpu_roofline_utilization")
        assert not any(k[0] == "t_fam_dis" for k in roof)


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
        # and the compile shim kept where each family's set-up went
        for fam in ("engine_ragged", "engine_decode", "train_step"):
            assert perf.compile_record(fam)["compiles"] >= 1

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
        perf.set_device_peaks(1e12, 1e11)    # only the flag says no
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
        assert not any(
            k[0] == "t_ov_perf"
            for k in _series("paddle_tpu_roofline_utilization"))
        assert tracing.events() == []
