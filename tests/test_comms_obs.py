"""Collective & mesh observability (paddle_tpu/observability/comms.py
+ the instrumented distributed/communication.py): per-collective
latency/bytes/bandwidth telemetry with completion-edge honesty, the
async Work.wait() timing fix, goodput accounting, the comms perf-ledger
families, the aggregator's cross-rank straggler attribution + the
`collective_skew` flight trigger — and the real spawn boundary: 8 rank
processes running an all_reduce loop with one rank delayed via the
resilience fault harness, attributed by the aggregator.

Module-level imports stay light: spawned children re-import this
module (spawn start method), and heavyweight imports belong inside
the functions that run after the JAX_PLATFORMS=cpu env guard."""
import json
import multiprocessing
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _comms_clean():
    """Every test starts disabled with empty stores, no injected
    faults, no armed flight recorder, and no peak overrides."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import flight, perf
    from paddle_tpu.resilience import faults
    obs.disable()
    obs.reset()
    faults.clear_all()
    yield
    from paddle_tpu.observability import fleet
    if fleet._AGGREGATOR is not None:
        fleet._AGGREGATOR.close()
    flight.disarm()
    faults.clear_all()
    perf.set_device_peaks()
    perf.set_interconnect_peaks()
    obs.disable()
    obs.reset()


def _series(name):
    from paddle_tpu import observability as obs
    rec = obs.snapshot().get(name)
    return rec["series"] if rec else {}


def _nonzero(name):
    out = {}
    for key, val in _series(name).items():
        if isinstance(val, dict):
            if val["count"]:
                out[key] = val
        elif val:
            out[key] = val
    return out


# ---------------------------------------------------------------------------
# eager collectives: every public op records (latency + bytes +
# launches + arrival), with completion-edge timing
# ---------------------------------------------------------------------------
class TestCollectiveTelemetry:
    def _world(self):
        import paddle_tpu.distributed as dist
        return dist.new_group()

    def test_every_eager_collective_records(self):
        import numpy as np
        import paddle_tpu as pt
        import paddle_tpu.distributed as dist
        from paddle_tpu import observability as obs
        obs.enable()
        g = self._world()
        n = g.nranks
        x = np.ones((n, 8 * n), np.float32)

        dist.all_reduce(pt.to_tensor(x))
        dist.reduce(pt.to_tensor(x), dst=0)
        dist.broadcast(pt.to_tensor(x), src=0)
        dist.all_gather(pt.to_tensor(x))
        gathered = []
        dist.all_gather(gathered, pt.to_tensor(x))
        dist.reduce_scatter(pt.to_tensor(x))
        dist.all_to_all(pt.to_tensor(x))
        outs = []
        dist.all_to_all(outs, [pt.to_tensor(x[i]) for i in range(n)])
        dist.scatter(pt.to_tensor(x), src=0)
        dist.barrier()
        dist.send(pt.to_tensor(x[0]), dst=g.ranks[-1])
        dist.recv(pt.to_tensor(np.zeros_like(x[0])), src=g.ranks[0])

        hist = _nonzero("paddle_tpu_collective_seconds")
        ops = {op for (op, grp) in hist}
        assert {"all_reduce", "reduce", "broadcast", "all_gather",
                "reduce_scatter", "all_to_all", "scatter", "barrier",
                "send", "recv"} <= ops
        assert all(grp == "world" for (_, grp) in hist)
        # all_gather ran twice (both call styles)
        assert hist[("all_gather", "world")]["count"] == 2
        launches = _nonzero("paddle_tpu_collective_launches_total")
        assert all(mode == "eager" for (_, mode) in launches)
        by = _nonzero("paddle_tpu_collective_bytes_total")
        assert by[("all_reduce",)] == x.nbytes / n   # per-rank payload
        assert by[("barrier",)] if ("barrier",) in by else True
        bw = _nonzero("paddle_tpu_collective_algbw_bytes_per_sec")
        assert bw[("all_reduce",)] > 0
        # spans + arrivals in the ring
        names = {e["name"] for e in obs.trace_events()}
        assert "comms.all_reduce" in names and "comms.arrival" in names
        arr = [e for e in obs.trace_events()
               if e["name"] == "comms.arrival"
               and e["args"]["op"] == "all_reduce"]
        assert arr[0]["args"]["group"] == "world"
        assert arr[0]["args"]["seq"] == 1

    def test_call_seq_increments_and_survives_reset(self):
        import numpy as np
        import paddle_tpu as pt
        import paddle_tpu.distributed as dist
        from paddle_tpu import observability as obs
        obs.enable()
        g = self._world()
        x = np.ones((g.nranks, 4), np.float32)
        dist.all_reduce(pt.to_tensor(x))
        dist.all_reduce(pt.to_tensor(x))
        seqs = [e["args"]["seq"] for e in obs.trace_events()
                if e["name"] == "comms.arrival"]
        first_pair = seqs[-2:]
        assert first_pair[1] == first_pair[0] + 1
        obs.reset()       # window reset must NOT reset the seq counter
        dist.all_reduce(pt.to_tensor(x))
        seqs2 = [e["args"]["seq"] for e in obs.trace_events()
                 if e["name"] == "comms.arrival"]
        assert seqs2 == [first_pair[1] + 1]

    def test_in_trace_collectives_count_only(self, monkeypatch):
        import numpy as np
        import paddle_tpu as pt
        from paddle_tpu import observability as obs
        from paddle_tpu.distributed import communication as comm
        obs.enable()
        comm.init_default_group()
        monkeypatch.setattr(comm, "_in_trace", lambda g: True)
        monkeypatch.setattr(comm.jax.lax, "psum",
                            lambda x, axis: x)
        comm.all_reduce(pt.to_tensor(np.ones((4,), np.float32)))
        launches = _nonzero("paddle_tpu_collective_launches_total")
        assert launches == {("all_reduce", "in_trace"): 1.0}
        # count-only: no latency sample, no arrival event, no span
        assert _nonzero("paddle_tpu_collective_seconds") == {}
        assert obs.trace_events() == []
        by = _nonzero("paddle_tpu_collective_bytes_total")
        assert by[("all_reduce",)] == 16.0   # the local view's bytes

    def test_ppermute_counts_in_trace(self, monkeypatch):
        import numpy as np
        import paddle_tpu as pt
        from paddle_tpu.distributed import communication as comm
        from paddle_tpu import observability as obs
        obs.enable()
        g = comm.init_default_group()
        monkeypatch.setattr(comm.jax.lax, "ppermute",
                            lambda x, axis, perm: x)
        comm.ppermute(pt.to_tensor(np.ones((2, 2), np.float32)), g,
                      [(0, 1)])
        launches = _nonzero("paddle_tpu_collective_launches_total")
        assert launches == {("ppermute", "in_trace"): 1.0}

    def test_async_wait_closes_timing_and_is_idempotent(self):
        import numpy as np
        import paddle_tpu as pt
        import paddle_tpu.distributed as dist
        from paddle_tpu import observability as obs
        obs.enable()
        g = self._world()
        x = np.ones((g.nranks, 16), np.float32)
        w = dist.all_reduce(pt.to_tensor(x), sync_op=False)
        # launch counted immediately; NO lating sample until wait()
        assert _nonzero("paddle_tpu_collective_launches_total")[
            ("all_reduce", "eager")] == 1.0
        assert _nonzero("paddle_tpu_collective_seconds") == {}
        time.sleep(0.02)
        assert w.wait() is True
        hist = _nonzero("paddle_tpu_collective_seconds")
        assert hist[("all_reduce", "world")]["count"] == 1
        # the span closed at wait(): duration covers launch->wait
        assert hist[("all_reduce", "world")]["min"] >= 0.02
        w.wait()          # double-wait: no second sample
        assert _nonzero("paddle_tpu_collective_seconds")[
            ("all_reduce", "world")]["count"] == 1

    def test_unwaited_async_counts_but_no_latency(self):
        import numpy as np
        import paddle_tpu as pt
        import paddle_tpu.distributed as dist
        from paddle_tpu import observability as obs
        obs.enable()
        g = self._world()
        x = np.ones((g.nranks, 4), np.float32)
        dist.all_reduce(pt.to_tensor(x), sync_op=False)   # dropped
        assert _nonzero("paddle_tpu_collective_launches_total")[
            ("all_reduce", "eager")] == 1.0
        assert _nonzero("paddle_tpu_collective_seconds") == {}

    def test_link_utilization_honest_about_unknown_device(self):
        import numpy as np
        import paddle_tpu as pt
        import paddle_tpu.distributed as dist
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import perf
        obs.enable()
        g = self._world()
        x = np.ones((g.nranks, 64), np.float32)
        dist.all_reduce(pt.to_tensor(x))
        # CPU box: no interconnect peak -> NO utilization series
        assert _nonzero("paddle_tpu_collective_link_utilization") == {}
        perf.set_interconnect_peaks(ici=1e9, dcn=1e8)
        dist.all_reduce(pt.to_tensor(x))
        util = _nonzero("paddle_tpu_collective_link_utilization")
        assert ("all_reduce", "ici") in util
        assert ("all_reduce", "dcn") in util
        bw = _series("paddle_tpu_collective_algbw_bytes_per_sec")[
            ("all_reduce",)]
        assert util[("all_reduce", "ici")] == pytest.approx(bw / 1e9)

    def test_fault_point_delays_arrival_and_span(self):
        """The comms.collective fault point fires before the arrival
        timestamp and inside the span window: a delayed rank's arrival
        is late AND its comms span covers the delay (the pair the
        straggler attribution + flight bundle acceptance rely on)."""
        import numpy as np
        import paddle_tpu as pt
        import paddle_tpu.distributed as dist
        from paddle_tpu import observability as obs
        from paddle_tpu.resilience import faults
        obs.enable()
        g = self._world()
        x = np.ones((g.nranks, 4), np.float32)
        t0 = time.perf_counter_ns() / 1000.0
        with faults.inject("comms.collective", delay=0.15,
                           match={"op": "all_reduce"}):
            dist.all_reduce(pt.to_tensor(x))
        arr = [e for e in obs.trace_events()
               if e["name"] == "comms.arrival"][-1]
        span = [e for e in obs.trace_events()
                if e["name"] == "comms.all_reduce"][-1]
        assert arr["ts"] - t0 >= 0.15e6          # arrival is late
        assert span["dur"] >= 0.15e6             # span covers the delay

    def test_disabled_mode_zero_alloc_instrumentation_layer(self):
        """Tracemalloc guard over the comms instrumentation entry
        points with observability off: start() returns None after one
        flag check, count/note_reshard/finish/Work.wait are no-ops —
        an absolute near-zero bound, so a per-op retained leak in the
        instrumentation layer cannot hide in a two-window delta. (The
        full collective bodies allocate through jax regardless of
        observability — measured identical on the uninstrumented
        revision — so the layer is guarded directly and the full paths
        by the records-nothing test below.)"""
        import tracemalloc
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import comms
        from paddle_tpu.distributed.communication import Work
        assert not obs.enabled()
        w = Work(None, None)

        def window(iters):
            tracemalloc.start()
            base = tracemalloc.get_traced_memory()[0]
            for _ in range(iters):
                rec = comms.start("all_reduce", "world", 64)
                comms.finish(rec)
                comms.count("all_reduce", "world", 64)
                comms.note_reshard("all_gather", "mp", 64)
                comms.note_train_step(0.1, None)
                w.wait()
            grown = tracemalloc.get_traced_memory()[0] - base
            tracemalloc.stop()
            return grown

        window(4000)        # warm call-site + interpreter residuals
        g1 = window(4000)
        g2 = window(4000)
        assert g2 < 1024, (g1, g2)
        assert abs(g2 - g1) < 1024, (g1, g2)

    def test_disabled_mode_records_nothing_across_every_collective(self):
        """Every instrumented collective path with observability off:
        no series, no trace events, no arrival marks, no window
        accumulation — the paths run, the instrumentation stays
        silent."""
        import numpy as np
        import paddle_tpu as pt
        import paddle_tpu.distributed as dist
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import comms, tracing
        assert not obs.enabled()
        g = self._world()
        n = g.nranks
        x = np.ones((n, 8 * n), np.float32)
        for _ in range(3):
            dist.all_reduce(pt.to_tensor(x))
            dist.reduce(pt.to_tensor(x), dst=0)
            dist.broadcast(pt.to_tensor(x), src=0)
            dist.all_gather(pt.to_tensor(x))
            dist.reduce_scatter(pt.to_tensor(x))
            dist.all_to_all(pt.to_tensor(x))
            dist.scatter(pt.to_tensor(x), src=0)
            dist.barrier()
            dist.send(pt.to_tensor(x[0]), dst=g.ranks[-1])
            dist.recv(pt.to_tensor(np.zeros_like(x[0])),
                      src=g.ranks[0])
            dist.all_reduce(pt.to_tensor(x), sync_op=False).wait()
        assert tracing.events() == []
        assert _nonzero("paddle_tpu_collective_seconds") == {}
        assert _nonzero("paddle_tpu_collective_launches_total") == {}
        assert _nonzero("paddle_tpu_collective_bytes_total") == {}
        assert comms._STEP_COMMS[0] == 0.0


# ---------------------------------------------------------------------------
# reshard sites (meta_parallel boundaries): count + bytes + marker
# ---------------------------------------------------------------------------
class TestReshardSites:
    def test_sequence_parallel_notes_reshards(self):
        import numpy as np
        from paddle_tpu import observability as obs
        from paddle_tpu.distributed import fleet as _fl
        from paddle_tpu.distributed.meta_parallel import (
            sequence_parallel as sp)
        from paddle_tpu.distributed.topology import (
            get_hybrid_communicate_group)
        hcg = get_hybrid_communicate_group()
        if hcg is None:
            import paddle_tpu.distributed as dist
            strategy = dist.fleet.DistributedStrategy()
            strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 8,
                                       "pp_degree": 1}
            dist.fleet.init(is_collective=True, strategy=strategy)
            hcg = get_hybrid_communicate_group()
        if "mp" not in getattr(hcg.mesh, "shape", {}):
            pytest.skip("ambient hybrid mesh (from an earlier test "
                        "file) lacks an mp axis")
        obs.enable()
        x = np.ones((2, 8, 4), np.float32)
        sp.scatter(x)
        sp.all_gather(x)
        sp.reduce_scatter(x)
        launches = _nonzero("paddle_tpu_collective_launches_total")
        assert launches[("scatter", "reshard")] == 1.0
        assert launches[("all_gather", "reshard")] == 1.0
        assert launches[("reduce_scatter", "reshard")] == 1.0
        # marker events, no latency histograms
        markers = [e for e in obs.trace_events()
                   if e["name"] == "comms.reshard"]
        assert {m["args"]["op"] for m in markers} == {
            "scatter", "all_gather", "reduce_scatter"}
        assert all(m["dur"] == 0.0 for m in markers)
        assert _nonzero("paddle_tpu_collective_seconds") == {}


# ---------------------------------------------------------------------------
# goodput accounting
# ---------------------------------------------------------------------------
class TestGoodput:
    def test_fractions_with_pinned_peaks(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import comms, perf
        obs.enable()
        perf.set_device_peaks(1e12, 1e11)
        # simulate: 40ms of comms inside a 100ms step whose cost model
        # implies 30ms of device time
        comms._STEP_COMMS[0] = 0.04
        cost = perf.CostModel(flops=3e10, bytes_accessed=1e9)
        comms.note_train_step(0.1, cost)
        good = _nonzero("paddle_tpu_train_goodput_fraction")
        assert good[("comms",)] == pytest.approx(0.4)
        assert good[("compute",)] == pytest.approx(0.3)
        assert good[("stall",)] == pytest.approx(0.3)
        # the accumulator was consumed
        assert comms._STEP_COMMS[0] == 0.0

    def test_unknown_device_publishes_comms_fraction_only(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import comms, perf
        obs.enable()
        assert perf.device_peaks() is None       # CPU box
        comms._STEP_COMMS[0] = 0.01
        comms.note_train_step(0.1, perf.CostModel(flops=1e9,
                                                  bytes_accessed=1e6))
        good = _nonzero("paddle_tpu_train_goodput_fraction")
        assert ("comms",) in good
        assert ("compute",) not in good and ("stall",) not in good

    def test_trainstep_emits_goodput(self):
        import numpy as np
        import paddle_tpu as pt
        from paddle_tpu import observability as obs
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.observability import perf
        obs.enable()
        perf.set_device_peaks(1e12, 1e11)
        lin = pt.nn.Linear(8, 8)
        step = TrainStep(lin, pt.optimizer.SGD(
            learning_rate=1e-3, parameters=lin.parameters()),
            lambda m, a: (m(a) ** 2).mean())
        xa = np.ones((4, 8), np.float32)
        for _ in range(5):
            step(xa)
        good = _series("paddle_tpu_train_goodput_fraction")
        assert ("comms",) in good                # sampled every step
        # compute/stall need the cost model; present when AOT worked
        if step._step_fn.expected is not None:
            assert ("compute",) in good and ("stall",) in good


# ---------------------------------------------------------------------------
# aggregator-side straggler attribution (in-process bundles)
# ---------------------------------------------------------------------------
def _arrival_ev(op, group, seq, ts_us):
    return {"name": "comms.arrival", "ph": "X", "pid": 1, "tid": 1,
            "ts": ts_us, "dur": 0.0,
            "args": {"op": op, "group": group, "seq": seq}}


def _bundle(proc, bseq, events):
    from paddle_tpu.observability import fleet
    return fleet.make_bundle(proc, "rank", bseq, trace=list(events))


class TestStragglerAttribution:
    def test_skew_and_straggler_published(self):
        from paddle_tpu.observability.fleet import FleetAggregator
        agg = FleetAggregator(straggler_threshold_s=0.5)
        agg.ingest(_bundle("r0", 1, [_arrival_ev("all_reduce", "world",
                                                 1, 1_000_000.0)]))
        agg.ingest(_bundle("r1", 1, [_arrival_ev("all_reduce", "world",
                                                 1, 1_050_000.0)]))
        snap = agg.registry.snapshot()
        assert snap["paddle_tpu_collective_skew_seconds"]["series"][
            ("all_reduce",)] == pytest.approx(0.05)
        # under threshold: nobody named
        st = snap.get("paddle_tpu_collective_straggler",
                      {"series": {}})["series"]
        assert not any(st.values())
        # the slow rank crosses the threshold late
        agg.ingest(_bundle("r2", 1, [_arrival_ev("all_reduce", "world",
                                                 1, 3_000_000.0)]))
        snap = agg.registry.snapshot()
        assert snap["paddle_tpu_collective_skew_seconds"]["series"][
            ("all_reduce",)] == pytest.approx(2.0)
        st = snap["paddle_tpu_collective_straggler"]["series"]
        flagged = {k for k, v in st.items() if v}
        assert flagged == {("all_reduce", "r2")}

    def test_straggler_clears_when_fleet_heals(self):
        from paddle_tpu.observability.fleet import FleetAggregator
        agg = FleetAggregator(straggler_threshold_s=0.5)
        agg.ingest(_bundle("r0", 1, [
            _arrival_ev("all_reduce", "world", 1, 0.0)]))
        agg.ingest(_bundle("r1", 1, [
            _arrival_ev("all_reduce", "world", 1, 2_000_000.0)]))
        st = agg.registry.snapshot()[
            "paddle_tpu_collective_straggler"]["series"]
        assert st[("all_reduce", "r1")] == 1.0
        # next collective: tight arrivals -> the flag clears
        agg.ingest(_bundle("r0", 2, [
            _arrival_ev("all_reduce", "world", 2, 5_000_000.0)]))
        agg.ingest(_bundle("r1", 2, [
            _arrival_ev("all_reduce", "world", 2, 5_001_000.0)]))
        st = agg.registry.snapshot()[
            "paddle_tpu_collective_straggler"]["series"]
        assert not any(st.values())

    def test_flight_bundle_once_per_key(self, tmp_path):
        from paddle_tpu.observability import flight
        from paddle_tpu.observability.fleet import FleetAggregator
        flight.arm(str(tmp_path / "fl"), collective_skew_s=1.0,
                   min_interval_s=0.0)
        agg = FleetAggregator(straggler_threshold_s=0.5)
        slow_span = {"name": "comms.all_reduce", "ph": "X", "pid": 9,
                     "tid": 1, "ts": 0.0, "dur": 2_000_000.0,
                     "args": {"group": "world", "bytes": 64}}
        agg.ingest(_bundle("r0", 1, [
            _arrival_ev("all_reduce", "world", 7, 0.0)]))
        agg.ingest(_bundle("r1", 1, [
            _arrival_ev("all_reduce", "world", 7, 2_000_000.0),
            slow_span]))
        bundles = flight.bundles()
        assert len(bundles) == 1
        assert "collective_skew" in os.path.basename(bundles[0])
        loaded = flight.load_bundle(bundles[0])
        assert loaded["meta"]["detail"]["straggler"] == "r1"
        assert loaded["meta"]["detail"]["op"] == "all_reduce"
        slow = [e for e in loaded["trace"]
                if e["name"] == "comms.all_reduce"
                and e["dur"] >= 1_000_000.0]
        assert slow, "flight trace must hold the slow collective span"
        # a third rank landing on the SAME key must not re-trigger
        agg.ingest(_bundle("r2", 1, [
            _arrival_ev("all_reduce", "world", 7, 2_500_000.0)]))
        assert len(flight.bundles()) == 1

    def test_arrival_table_bounded(self):
        from paddle_tpu.observability.fleet import FleetAggregator
        agg = FleetAggregator()
        cap = agg.ARRIVAL_KEY_CAP
        evs = [_arrival_ev("all_reduce", "world", i, float(i))
               for i in range(cap + 10)]
        agg.ingest(_bundle("r0", 1, evs))
        assert len(agg._arrivals) == cap


# ---------------------------------------------------------------------------
# obs_top "== comms ==" panel
# ---------------------------------------------------------------------------
class TestObsTopCommsPanel:
    def _obs_top(self):
        tools = os.path.join(REPO, "tools")
        sys.path.insert(0, tools)
        try:
            import obs_top
        finally:
            sys.path.remove(tools)
        return obs_top

    def test_renders_ops_goodput_and_straggler(self):
        import numpy as np
        import paddle_tpu as pt
        import paddle_tpu.distributed as dist
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import comms, perf
        from paddle_tpu.observability.fleet import FleetAggregator
        obs_top = self._obs_top()
        obs.enable()
        perf.set_device_peaks(1e12, 1e11)
        g = dist.new_group()
        x = np.ones((g.nranks, 64), np.float32)
        prev = json.loads(obs.to_json())
        for _ in range(3):
            dist.all_reduce(pt.to_tensor(x))
        comms._STEP_COMMS[0] = 0.02
        comms.note_train_step(0.1, perf.CostModel(
            flops=3e10, bytes_accessed=1e9))
        doc = json.loads(obs.to_json())
        frame = obs_top.render(doc, prev, dt=1.0)
        assert "== comms ==" in frame
        line = [ln for ln in frame.splitlines()
                if ln.strip().startswith("all_reduce")][0]
        assert "p50=" in line and "MB/s" in line
        assert "goodput" in frame and "compute=" in frame
        # straggler view from an aggregator export
        agg = FleetAggregator(straggler_threshold_s=0.5)
        agg.ingest(_bundle("r0", 1, [
            _arrival_ev("all_reduce", "world", 1, 0.0)]))
        agg.ingest(_bundle("r5", 1, [
            _arrival_ev("all_reduce", "world", 1, 1_500_000.0)]))
        fdoc = json.loads(agg.to_json())
        fframe = obs_top.render(fdoc)
        assert "skew" in fframe and "straggler=r5" in fframe

    def test_no_comms_series_renders_no_panel(self):
        obs_top = self._obs_top()
        assert "== comms ==" not in obs_top.render({})


# ---------------------------------------------------------------------------
# the real spawn boundary: 8 rank processes, one delayed all_reduce,
# attributed by the aggregator — and no false straggler when clean
# ---------------------------------------------------------------------------
def _rank_worker(endpoint, name, barrier, straggle, q):
    """Spawned rank: warms its all_reduce with observability OFF (so
    startup staggering never enters the arrival record), then runs a
    clean lockstep round and a second round where one rank injects a
    comms.collective delay, shipping bundles after each round."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        import numpy as np
        import paddle_tpu as pt
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet
        from paddle_tpu.resilience import faults
        import paddle_tpu.distributed as dist

        g = dist.new_group()
        x = np.ones((g.nranks, 512), np.float32)
        dist.all_reduce(pt.to_tensor(x))        # warm, unrecorded
        fleet.set_identity(process=name, role="rank")
        agent = fleet.FleetAgent(endpoint, interval_s=3600.0,
                                 timeout_s=60.0)
        obs.enable()
        barrier.wait(timeout=600)               # clean round, lockstep
        for _ in range(2):
            dist.all_reduce(pt.to_tensor(x))
        ok1 = agent.ship()
        barrier.wait(timeout=600)               # parent asserts clean
        barrier.wait(timeout=600)               # delayed round starts
        if straggle:
            faults.inject("comms.collective", delay=1.5, times=1,
                          match={"op": "all_reduce"})
        dist.all_reduce(pt.to_tensor(x))
        ok2 = agent.ship()
        q.put((name, bool(ok1 and ok2)))
    except BaseException as e:                  # report, don't hang
        q.put((name, f"ERROR: {e!r}"))
        raise


class TestMultiProcessStraggler:
    def test_eight_rank_all_reduce_delay_attributed(self, tmp_path):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet, flight
        obs.enable()
        flight.arm(str(tmp_path / "flight"), collective_skew_s=1.0,
                   min_interval_s=0.0)
        agg = fleet.serve_aggregator(stale_after_s=600.0,
                                     straggler_threshold_s=0.5)
        ctx = multiprocessing.get_context("spawn")
        barrier = ctx.Barrier(9)
        q = ctx.Queue()
        names = [f"rank{i}" for i in range(8)]
        procs = [ctx.Process(target=_rank_worker,
                             args=(agg.endpoint, n, barrier,
                                   n == "rank5", q))
                 for n in names]
        for p in procs:
            p.start()
        try:
            barrier.wait(timeout=600)     # workers warm; clean round
            barrier.wait(timeout=600)     # all clean bundles shipped
            snap = agg.registry.snapshot()
            skews = snap["paddle_tpu_collective_skew_seconds"][
                "series"]
            assert skews[("all_reduce",)] < 0.5, skews
            st = snap.get("paddle_tpu_collective_straggler",
                          {"series": {}})["series"]
            assert not any(st.values()), \
                f"false straggler on the clean run: {st}"
            assert flight.bundles() == []
            barrier.wait(timeout=600)     # release the delayed round
            reports = dict(q.get(timeout=300) for _ in range(8))
            assert all(v is True for v in reports.values()), reports
        finally:
            for p in procs:
                p.join(120)
                if p.is_alive():
                    p.kill()
        # the delayed rank is named, exactly once, with the evidence
        snap = agg.registry.snapshot()
        assert snap["paddle_tpu_collective_skew_seconds"]["series"][
            ("all_reduce",)] >= 1.0
        st = snap["paddle_tpu_collective_straggler"]["series"]
        flagged = {k for k, v in st.items() if v}
        assert flagged == {("all_reduce", "rank5")}
        bundles = flight.bundles()
        skew_bundles = [b for b in bundles
                        if "collective_skew" in os.path.basename(b)]
        assert len(skew_bundles) == 1, bundles
        loaded = flight.load_bundle(skew_bundles[0])
        assert loaded["meta"]["detail"]["straggler"] == "rank5"
        slow = [e for e in loaded["trace"]
                if e["name"] == "comms.all_reduce"
                and e["dur"] >= 1_000_000.0]
        assert slow, \
            "the flight trace must hold the slow comms.all_reduce span"
