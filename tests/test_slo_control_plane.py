"""Serving SLO control plane (observability/slo_fleet.py,
inference/autoscaler.py, inference/traffic.py + the router's elastic
surface): fleet-wide SLO evaluation over process-merged request
series, the TTFT latency-budget invariant, the SLO-driven autoscaler's
hysteresis/journal/bundle contract, and the deterministic traffic
harness.

Oracles: the TTFT budget components must sum EXACTLY to the TTFT
observation (both sides are computed from the same perf_counter reads,
so equality is bitwise, not approximate); the fleet monitor's windowed
attained fractions against hand-built bucket vectors; the autoscaler
against a scripted monitor (every decision's cause is pinned)."""
import json
import multiprocessing
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.inference import (Autoscaler, LLMEngine, Router,
                                  RouterActuator, TrafficModel)
from paddle_tpu.models import GPTForCausalLM
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.observability import flight
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import slo, slo_fleet


@pytest.fixture(scope="module")
def tiny_gpt():
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _clean():
    obs.disable()
    obs.reset()
    flight.disarm()
    yield
    flight.disarm()
    obs.disable()
    obs.reset()


def _engine_factory(model):
    def make(_i):
        return LLMEngine(model, max_batch=2, block_size=16,
                         decode_chunk=4, prompt_quantum=16,
                         max_model_len=64)
    return make


def _prompts(n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1024, (k,)).astype(np.int32)
            for k in (5, 9, 13, 21, 7, 15)[:n]]


# ---------------------------------------------------------------------------
# TTFT latency budget: components sum exactly to TTFT
# ---------------------------------------------------------------------------
class TestTTFTBudget:
    BUDGET_COMPONENTS = {"queue_wait", "prefill_compute",
                         "affinity_miss", "compile_stall", "other"}

    def test_components_sum_exactly_to_ttft(self, tiny_gpt):
        obs.enable()
        eng = _engine_factory(tiny_gpt)(0)
        eng.generate(_prompts(4), max_new_tokens=6)
        r = om.registry()
        ttft = r.get("paddle_tpu_request_ttft_seconds")
        child = ttft._children.get(())
        assert child is not None and child._count == 4
        bud = r.get("paddle_tpu_request_ttft_budget_seconds")
        comps = {key[0]: c for key, c in bud._series()}
        # every observed component is a known one, and the two big
        # mandatory ones are always present
        assert set(comps) <= self.BUDGET_COMPONENTS
        assert {"queue_wait", "prefill_compute"} <= set(comps)
        # the invariant the dashboards divide by: component sums ==
        # TTFT sum EXACTLY (same perf_counter reads on both sides,
        # the remainder lands in "other" by construction)
        total = sum(c._sum for c in comps.values())
        assert total == pytest.approx(child._sum, abs=1e-9)
        # per-request observation parity on every observed component:
        # one observation per request
        for name, c in comps.items():
            assert c._count == child._count, name

    def test_budget_empty_when_disabled(self, tiny_gpt):
        assert not obs.enabled()
        eng = _engine_factory(tiny_gpt)(0)
        eng.generate(_prompts(2), max_new_tokens=4)
        bud = om.registry().get(
            "paddle_tpu_request_ttft_budget_seconds")
        if bud is not None:     # registered at import, never observed
            assert sum(c._count for _, c in bud._series()) == 0


# ---------------------------------------------------------------------------
# FleetSLOMonitor: windowed verdicts, episode latch, attribution
# ---------------------------------------------------------------------------
def _proc_hist(reg):
    return reg.histogram("paddle_tpu_request_ttft_seconds",
                         "test ttft", ("process",))


class TestFleetSLOMonitor:
    def _rule(self, thr=0.5, objective=0.9):
        return slo.SLO("ttft_p95", "paddle_tpu_request_ttft_seconds",
                       threshold_s=thr, objective=objective)

    def test_fleet_sum_and_worst_process_attribution(self):
        obs.enable()
        reg = om.MetricsRegistry()      # aggregator-style registry
        h = _proc_hist(reg)
        for _ in range(40):
            h.labels(process="fast").observe(0.01)
        for _ in range(40):
            h.labels(process="slow").observe(2.0)
        mon = slo_fleet.FleetSLOMonitor(
            registry=reg, rules=[self._rule()],
            flight_on_breach=False)
        (res,) = mon.evaluate()
        assert not res.ok and res.count == 80
        assert res.attained == pytest.approx(0.5, abs=0.05)
        assert res.worst_process == "slow"
        assert res.per_process["fast"] == pytest.approx(1.0, abs=0.02)
        assert res.per_process["slow"] == pytest.approx(0.0, abs=0.02)
        # verdict gauges published into the evaluated registry
        snap = reg.snapshot()
        assert snap["paddle_tpu_slo_attained_fraction"]["series"][
            ("ttft_p95",)] == res.attained
        assert snap["paddle_tpu_slo_objective_fraction"]["series"][
            ("ttft_p95",)] == 0.9

    def test_windowed_delta_sees_only_new_observations(self):
        obs.enable()
        reg = om.MetricsRegistry()
        h = _proc_hist(reg)
        for _ in range(50):
            h.labels(process="p0").observe(2.0)    # breaching history
        mon = slo_fleet.FleetSLOMonitor(
            registry=reg, rules=[self._rule()],
            flight_on_breach=False)
        (r1,) = mon.evaluate()
        assert not r1.ok and r1.count == 50
        # window 2: only fast traffic arrives — the cumulative
        # distribution is still poisoned, the window is clean
        for _ in range(50):
            h.labels(process="p0").observe(0.01)
        (r2,) = mon.evaluate()
        assert r2.ok and r2.count == 50
        assert r2.attained == pytest.approx(1.0, abs=0.02)
        # idle window: vacuous, not a breach
        (r3,) = mon.evaluate()
        assert r3.ok and r3.attained is None and r3.count == 0

    def test_min_count_makes_thin_windows_vacuous(self):
        obs.enable()
        reg = om.MetricsRegistry()
        h = _proc_hist(reg)
        mon = slo_fleet.FleetSLOMonitor(
            registry=reg, rules=[self._rule()],
            min_count=5, flight_on_breach=False)
        mon.evaluate()
        h.labels(process="p0").observe(2.0)
        (res,) = mon.evaluate()
        assert res.ok and res.attained is None

    def test_breach_episode_dumps_one_bundle(self, tmp_path):
        obs.enable()
        flight.arm(str(tmp_path))
        reg = om.MetricsRegistry()
        h = _proc_hist(reg)
        mon = slo_fleet.FleetSLOMonitor(
            registry=reg, rules=[self._rule()])
        mon.evaluate()                      # prime the window

        def bundles():
            return sorted(p for p in os.listdir(str(tmp_path))
                          if p.startswith("bundle_"))

        for _ in range(20):
            h.labels(process="slow").observe(2.0)
        mon.evaluate()                      # ok -> breach: one bundle
        assert len(bundles()) == 1
        assert "slo_breach" in bundles()[0]
        for _ in range(20):
            h.labels(process="slow").observe(2.0)
        mon.evaluate()                      # still breaching: latched
        assert len(bundles()) == 1
        for _ in range(60):
            h.labels(process="slow").observe(0.01)
        mon.evaluate()                      # recovered
        for _ in range(20):
            h.labels(process="slow").observe(2.0)
        mon.evaluate()                      # NEW episode: second bundle
        assert len(bundles()) == 2
        # the bundle's detail attributes the breach
        with open(os.path.join(str(tmp_path), bundles()[0],
                               "meta.json")) as f:
            meta = json.load(f)
        assert meta["reason"] == "slo_breach"
        assert meta["detail"]["worst_process"] == "slow"
        assert meta["detail"]["scope"] == "fleet"
        assert meta["detail"]["threshold_s"] == 0.5
        # breaches_total counts EVALUATIONS (3), not episodes (2)
        snap = om.registry().snapshot()
        assert snap["paddle_tpu_slo_breaches_total"]["series"][
            ("ttft_p95",)] == 3.0


# ---------------------------------------------------------------------------
# cross-process: two spawned replicas ship skewed latencies, the
# monitor over the aggregator attributes the breach to the slow one
# ---------------------------------------------------------------------------
def _slo_worker(endpoint, name, lat_s, n, q):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        from paddle_tpu import observability as wobs
        from paddle_tpu.observability import fleet as wfleet
        wobs.enable()
        wfleet.set_identity(process=name, role="engine")
        h = wobs.registry().histogram(
            "paddle_tpu_request_ttft_seconds", "test ttft")
        for _ in range(n):
            h.observe(lat_s)
        agent = wfleet.FleetAgent(endpoint, interval_s=60.0,
                                  timeout_s=30.0)
        ok = agent.ship()
        agent.stop()
        q.put((name, bool(ok)))
    except BaseException as e:      # report instead of hanging parent
        q.put((name, f"ERROR: {e!r}"))
        raise


class TestCrossProcessSLO:
    def test_breach_attributes_slow_process_one_bundle(self, tmp_path):
        from paddle_tpu.observability import fleet
        obs.enable()
        flight.arm(str(tmp_path))
        agg = fleet.serve_aggregator(stale_after_s=60.0)
        try:
            ctx = multiprocessing.get_context("spawn")
            q = ctx.Queue()
            ws = [ctx.Process(target=_slo_worker,
                              args=(agg.endpoint, nm, lat, 40, q))
                  for nm, lat in (("fast-rep", 0.01),
                                  ("slow-rep", 2.0))]
            for w in ws:
                w.start()
            reports = dict(q.get(timeout=180) for _ in ws)
            for w in ws:
                w.join(60)
            assert reports == {"fast-rep": True, "slow-rep": True}, \
                reports
            mon = slo_fleet.FleetSLOMonitor(agg=agg, rules=[
                slo.SLO("ttft_p95",
                        "paddle_tpu_request_ttft_seconds",
                        threshold_s=0.5, objective=0.95)])
            (res,) = mon.evaluate()
            assert not res.ok and res.count == 80
            assert res.attained == pytest.approx(0.5, abs=0.05)
            assert res.worst_process == "slow-rep"
            assert res.per_process["fast-rep"] == pytest.approx(
                1.0, abs=0.02)
            bundles = [p for p in os.listdir(str(tmp_path))
                       if p.startswith("bundle_")]
            assert len(bundles) == 1 and "slo_breach" in bundles[0]
            # idle window after the breach: no new bundle, latched
            (res2,) = mon.evaluate()
            assert res2.ok and res2.attained is None
            assert len([p for p in os.listdir(str(tmp_path))
                        if p.startswith("bundle_")]) == 1
        finally:
            agg.close()


# ---------------------------------------------------------------------------
# Autoscaler: hysteresis, journal, exactly-one-bundle-per-decision
# ---------------------------------------------------------------------------
class _ScriptedMonitor:
    """A FleetSLOMonitor stand-in whose evaluate() pops scripted
    verdicts: 'breach', 'calm' (comfortably above objective), 'ok'
    (above objective but inside the retire margin), 'idle' (vacuous)."""

    def __init__(self, script):
        self.registry = om.registry()
        self.script = list(script)
        self.rule = slo.SLO("ttft_p95",
                            "paddle_tpu_request_ttft_seconds",
                            threshold_s=0.5, objective=0.9)

    def evaluate(self):
        kind = self.script.pop(0) if self.script else "idle"
        att = {"breach": 0.4, "calm": 1.0, "ok": 0.905,
               "idle": None}[kind]
        return [slo_fleet.FleetSLOResult(
            self.rule, att, 0 if att is None else 100,
            per_process={"p0": att} if att is not None else {},
            worst_process="p0" if att is not None else None)]


class _ScriptedActuator:
    def __init__(self, n=1, refuse_grows=0):
        self.n = n
        self.log = []
        self.refuse_grows = refuse_grows

    def grow(self):
        if self.refuse_grows > 0:       # spawn still pending
            self.refuse_grows -= 1
            self.log.append("grow-refused")
            return None
        self.n += 1
        self.log.append("grow")
        return "replica-%d" % self.n

    def retire(self):
        self.n -= 1
        self.log.append("retire")
        return "replica-%d" % (self.n + 1)

    def replicas(self):
        return self.n


class TestAutoscaler:
    def test_grow_after_streak_with_trigger_and_journal(self, tmp_path):
        obs.enable()
        mon = _ScriptedMonitor(["breach"] * 4)
        act = _ScriptedActuator()
        journal = str(tmp_path / "scale.jsonl")
        asc = Autoscaler(act, mon, max_replicas=3, grow_after=3,
                         cooldown_scans=0, journal_path=journal)
        assert asc.scan() is None and asc.scan() is None
        dec = asc.scan()                # third consecutive breach
        assert dec is not None and dec["action"] == "grow"
        assert dec["replicas_before"] == 1
        assert dec["replicas_after"] == 2
        assert dec["trigger"]["slo"] == "ttft_p95"
        assert dec["trigger"]["threshold_s"] == 0.5
        assert dec["trigger"]["worst_process"] == "p0"
        assert act.log == ["grow"]
        with open(journal) as f:
            recs = [json.loads(ln) for ln in f]
        assert [r["state"] for r in recs] == ["pending", "committed"]
        assert all(r["action"] == "grow" for r in recs)
        # streak reset on commit: the 4th breach alone can't re-grow
        assert asc.scan() is None

    def test_exactly_one_bundle_per_decision_zero_on_steady(
            self, tmp_path):
        obs.enable()
        flight.arm(str(tmp_path / "flight"))
        os.makedirs(str(tmp_path / "flight"), exist_ok=True)

        def bundles():
            return [p for p in os.listdir(str(tmp_path / "flight"))
                    if p.startswith("bundle_")]

        # steady load: every scan comfortable, fleet at min — zero
        # decisions, zero bundles
        asc = Autoscaler(_ScriptedActuator(),
                         _ScriptedMonitor(["calm"] * 6),
                         retire_after=2, cooldown_scans=0)
        for _ in range(6):
            assert asc.scan() is None   # n==min_replicas: no retire
        assert bundles() == []
        assert asc.decisions == []
        # breach -> grow -> recover -> retire: exactly two bundles,
        # one per committed decision
        mon = _ScriptedMonitor(["breach", "breach"] + ["calm"] * 3)
        act = _ScriptedActuator()
        asc = Autoscaler(act, mon, grow_after=2, retire_after=3,
                         cooldown_scans=0, max_replicas=3)
        decs = [asc.scan() for _ in range(5)]
        committed = [d for d in decs if d is not None]
        assert [d["action"] for d in committed] == ["grow", "retire"]
        names = sorted(bundles())
        assert len(names) == 2
        assert all("autoscale_decision" in n for n in names)
        with open(os.path.join(str(tmp_path / "flight"), names[0],
                               "meta.json")) as f:
            meta = json.load(f)
        assert meta["detail"]["action"] == "grow"
        assert meta["detail"]["trigger"]["series"] == \
            "paddle_tpu_request_ttft_seconds"

    def test_aborted_grow_keeps_streak_and_retries(self, tmp_path):
        """The async-actuator contract: a grow that returns None
        (spawn still pending) journals an abort but must NOT reset
        the breach streak or start a cooldown — the very next scan
        retries and commits once the replica is ready."""
        obs.enable()
        mon = _ScriptedMonitor(["breach"] * 5)
        act = _ScriptedActuator(refuse_grows=2)
        journal = str(tmp_path / "scale.jsonl")
        asc = Autoscaler(act, mon, grow_after=2, cooldown_scans=2,
                         journal_path=journal)
        assert asc.scan() is None       # streak 1: observe
        assert asc.scan() is None       # streak 2: grow -> refused
        assert asc.scan() is None       # retry -> refused
        dec = asc.scan()                # retry -> committed
        assert dec is not None and dec["action"] == "grow"
        assert act.log == ["grow-refused", "grow-refused", "grow"]
        with open(journal) as f:
            states = [json.loads(ln)["state"] for ln in f]
        assert states == ["pending", "aborted", "pending", "aborted",
                          "pending", "committed"]
        # cooldown armed only by the COMMIT
        assert asc.scan() is None

    def test_ceiling_floor_and_cooldown(self):
        obs.enable()
        act = _ScriptedActuator(n=3)
        asc = Autoscaler(act, _ScriptedMonitor(["breach"] * 4),
                         max_replicas=3, grow_after=1,
                         cooldown_scans=0)
        for _ in range(4):
            assert asc.scan() is None   # at ceiling: never grows
        assert act.log == []
        act = _ScriptedActuator(n=2)
        asc = Autoscaler(act, _ScriptedMonitor(
            ["calm", "calm", "breach", "breach"]),
            min_replicas=1, max_replicas=3, grow_after=1,
            retire_after=2, cooldown_scans=2)
        assert asc.scan() is None
        dec = asc.scan()
        assert dec is not None and dec["action"] == "retire"
        # cooldown: the following breaches are observed, not acted on
        assert asc.scan() is None and asc.scan() is None
        snap = om.registry().snapshot()
        assert snap["paddle_tpu_autoscaler_replicas"]["series"][
            ()] == 1.0
        assert snap["paddle_tpu_autoscaler_decisions_total"]["series"][
            ("retire",)] == 1.0
        assert snap["paddle_tpu_autoscaler_last_decision"]["series"][
            ("retire",)] == 1.0

    def test_ok_inside_margin_is_not_calm(self):
        """Attained above objective but inside retire_margin must
        neither grow nor retire — the hysteresis dead band."""
        obs.enable()
        act = _ScriptedActuator(n=2)
        asc = Autoscaler(act, _ScriptedMonitor(["ok"] * 5),
                         retire_after=1, retire_margin=0.02,
                         cooldown_scans=0)
        for _ in range(5):
            assert asc.scan() is None
        assert act.log == []


# ---------------------------------------------------------------------------
# the router's elastic surface (what the actuator actuates)
# ---------------------------------------------------------------------------
class TestElasticRouter:
    def test_grow_serves_and_retire_drains_onto_survivors(
            self, tiny_gpt):
        obs.enable()
        router = Router(_engine_factory(tiny_gpt), n_replicas=1)
        single = LLMEngine(tiny_gpt, max_batch=2, block_size=16,
                           decode_chunk=4, prompt_quantum=16,
                           max_model_len=64)
        prompts = _prompts(4)
        want = {str(i): r.output_ids for i, r in enumerate(
            single.generate(prompts, max_new_tokens=6))}
        grown = router.add_replica()
        assert grown == "replica-1" and len(router.replicas) == 2
        assert router.stats["grown"] == 1
        for i, p in enumerate(prompts):
            router.submit(str(i), p, max_new_tokens=6)
        # retire mid-flight: victims must re-serve on the survivor
        # bit-identically (greedy decode is deterministic)
        retired = router.retire_replica(grown)
        assert retired == grown
        assert router.stats["retired"] == 1
        done = {}
        while router.has_unfinished:
            for r in router.step():
                done[r.request_id] = r
        assert len(done) == 4
        for rid, r in done.items():
            assert r.ok, (rid, r.error)
            np.testing.assert_array_equal(r.output_ids, want[rid])
        # the retired replica's state gauges read 0 (exports stop
        # naming it as live)
        snap = om.registry().snapshot()
        states = snap["paddle_tpu_router_replica_state"]["series"]
        assert states[(grown, "healthy")] == 0.0
        assert states[(grown, "dead")] == 0.0

    def test_never_retires_last_live_replica(self, tiny_gpt):
        router = Router(_engine_factory(tiny_gpt), n_replicas=1)
        assert router.retire_replica() is None
        assert len(router.replicas) == 1

    def test_engine_factory_override_attaches_preprovisioned(
            self, tiny_gpt):
        """The async-grow path: an actuator that spawned the engine
        out-of-band attaches the READY engine through the override —
        the router must use it, not the construction factory."""
        calls = []

        def counting_factory(i):
            calls.append(i)
            return _engine_factory(tiny_gpt)(i)

        router = Router(counting_factory, n_replicas=1)
        assert calls == [0]
        pre = _engine_factory(tiny_gpt)(99)
        router.add_replica(engine_factory=lambda _i, e=pre: e)
        assert calls == [0]             # construction factory unused
        assert router.replicas.handles[1].engine is pre
        done = _serve_all(router, _prompts(2), 4)
        assert all(r.ok for r in done.values())

    def test_replica_seconds_accumulates_retirees(self, tiny_gpt):
        router = Router(_engine_factory(tiny_gpt), n_replicas=2)
        time.sleep(0.05)
        before = router.replica_seconds()
        assert before >= 0.1            # 2 replicas x >=0.05s
        router.retire_replica()
        after = router.replica_seconds()
        assert after >= before
        time.sleep(0.05)
        # the retiree's clock stopped; the survivor's keeps running
        assert router.replica_seconds() - after == pytest.approx(
            0.05, abs=0.04)

    def test_retire_shuts_down_process_like_engine(self):
        stops = []

        class _FakeEngine:
            def __init__(self):
                self.has_unfinished = False

            def add_request(self, *a, **k):
                pass

            def step(self):
                return []

            def abort_request(self, rid):
                return False

            def shutdown(self):
                stops.append(True)

        router = Router(lambda i: _FakeEngine(), n_replicas=2)
        router.retire_replica()
        assert stops == [True]

    def test_concurrent_stepping_for_safe_engines(self):
        """Engines that declare concurrent_step_safe are stepped on
        pool threads (process-backed fleets overlap their compute);
        default engines keep the sequential router-thread path."""
        threads = set()

        class _Eng:
            def __init__(self, safe):
                if safe:
                    self.concurrent_step_safe = True
                self.pending = []

            @property
            def has_unfinished(self):
                return bool(self.pending)

            def add_request(self, rid, prompt, max_new, **kw):
                self.pending.append((rid, prompt))

            def step(self):
                threads.add(threading.current_thread().name)
                from paddle_tpu.inference.llm_engine import \
                    GenerationResult
                out = [GenerationResult(
                    request_id=rid, prompt_ids=p,
                    output_ids=np.zeros((2,), np.int32),
                    finish_reason="length", error=None)
                    for rid, p in self.pending]
                self.pending.clear()
                return out

            def abort_request(self, rid):
                return False

        for safe in (True, False):
            threads.clear()
            router = Router(lambda i, s=safe: _Eng(s), n_replicas=3,
                            affinity=False)
            for i, p in enumerate(_prompts(6)):
                router.submit(i, p, max_new_tokens=2)
            done = {}
            while router.has_unfinished:
                for r in router.step():
                    done[r.request_id] = r
            assert len(done) == 6 and all(r.ok for r in done.values())
            on_pool = [t for t in threads
                       if t.startswith("router-step")]
            if safe:
                assert on_pool, threads
            else:
                assert not on_pool, threads


def _serve_all(router, prompts, n_new):
    for i, p in enumerate(prompts):
        router.submit(f"g{i}", p, max_new_tokens=n_new)
    done = {}
    while router.has_unfinished:
        for r in router.step():
            done[r.request_id] = r
    return done


# ---------------------------------------------------------------------------
# traffic generator: determinism
# ---------------------------------------------------------------------------
class TestTrafficModel:
    def test_deterministic_across_instances(self):
        a = list(TrafficModel(seed=11).events(60))
        b = list(TrafficModel(seed=11).events(60))
        assert len(a) == 60
        for ea, eb in zip(a, b):
            assert ea.rid == eb.rid and ea.t == eb.t
            assert ea.cohort == eb.cohort and ea.session == eb.session
            assert ea.max_new == eb.max_new
            np.testing.assert_array_equal(ea.prompt, eb.prompt)

    def test_seeds_and_cohort_mix_differ(self):
        a = list(TrafficModel(seed=1).events(80))
        b = list(TrafficModel(seed=2).events(80))
        assert any(ea.rid != eb.rid or len(ea.prompt) != len(eb.prompt)
                   for ea, eb in zip(a, b))
        assert len({e.cohort for e in a}) >= 2   # heavy-tailed mix
        # multi-turn sessions exist: some session recurs
        sessions = [e.session for e in a if e.session is not None]
        assert len(sessions) > len(set(sessions))


# ---------------------------------------------------------------------------
# quantiles_by_label (promoted metrics helper)
# ---------------------------------------------------------------------------
class TestQuantilesByLabel:
    def test_per_label_aggregation_and_window_delta(self):
        obs.enable()
        h = om.registry().histogram("t_qbl_seconds", "",
                                    ("op", "group"))
        for _ in range(40):
            h.labels(op="fast", group="g0").observe(0.01)
            h.labels(op="fast", group="g1").observe(0.012)
            h.labels(op="slow", group="g0").observe(1.0)
        doc = json.loads(om.registry().to_json())
        out = om.quantiles_by_label(doc, "t_qbl_seconds", "op")
        # the two fast groups merged under one label value
        assert out["fast"]["count"] == 80
        assert out["slow"]["count"] == 40
        assert out["fast"]["p95"] < 0.1 < out["slow"]["p50"]
        # windowed read: only the delta since `prev` counts
        for _ in range(10):
            h.labels(op="slow", group="g0").observe(0.01)
        doc2 = json.loads(om.registry().to_json())
        win = om.quantiles_by_label(doc2, "t_qbl_seconds", "op",
                                    prev=doc)
        assert win["slow"]["count"] == 10
        assert win["slow"]["p95"] < 0.1
        # absent metric / non-histogram: empty, not a crash
        assert om.quantiles_by_label(doc, "nope", "op") == {}


# ---------------------------------------------------------------------------
# tools: known_failures --staleness audit + obs_top slo panel
# ---------------------------------------------------------------------------
def _tools_mod(name):
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        return __import__(name)
    finally:
        sys.path.remove(tools)


class TestKnownFailuresStaleness:
    def test_buckets(self, tmp_path):
        kf = _tools_mod("known_failures")
        d = tmp_path / "tests"
        d.mkdir()
        (d / "test_alive.py").write_text(
            "def test_still_failing():\n    pass\n"
            "def test_now_passing():\n    pass\n")
        manifest = {
            "failures": [
                "tests/test_alive.py::test_still_failing",
                "tests/test_alive.py::test_renamed_away",
                "tests/test_gone.py::test_anything",
            ],
            "flaky": ["tests/test_alive.py::test_now_passing[x-1]"],
        }
        out = kf.classify_staleness(
            manifest,
            failed=["tests/test_alive.py::test_still_failing"],
            root=str(tmp_path))
        assert out["file_missing"] == [
            "tests/test_gone.py::test_anything"]
        assert out["test_missing"] == [
            "tests/test_alive.py::test_renamed_away"]
        # parametrized id resolves to the bare function name
        assert out["absent_this_run"] == [
            "tests/test_alive.py::test_now_passing[x-1]"]


class TestObsTopSLOPanel:
    def test_renders_verdicts_budget_and_autoscaler(self, tiny_gpt):
        obs_top = _tools_mod("obs_top")
        obs.enable()
        # real series from the real stack: engine traffic + monitor +
        # autoscaler accounting
        eng = _engine_factory(tiny_gpt)(0)
        eng.generate(_prompts(2), max_new_tokens=4)
        mon = slo_fleet.FleetSLOMonitor(
            registry=om.registry(), flight_on_breach=False,
            rules=[slo.SLO("ttft_p95",
                           "paddle_tpu_request_ttft_seconds",
                           threshold_s=10.0, objective=0.9)])
        mon.evaluate()
        asc = Autoscaler(_ScriptedActuator(n=2),
                         _ScriptedMonitor([]), cooldown_scans=0)
        asc.scan()
        frame = obs_top.render(json.loads(obs.to_json()))
        assert "== slo ==" in frame
        assert "ttft_p95" in frame and "ok" in frame
        assert "ttft budget" in frame
        assert "prefill_compute" in frame
        assert "replicas=2" in frame

    def test_absent_without_slo_series(self):
        obs_top = _tools_mod("obs_top")
        assert "== slo ==" not in obs_top.render({})
