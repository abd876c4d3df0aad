"""Replicated serving with failover (inference/router.py): a
health-checked Router over N in-process LLMEngine replicas,
chaos-tested.

Oracle: a single never-killed LLMEngine (itself oracle-pinned against
models.generation.generate in test_llm_engine). Greedy decoding is
deterministic, so every accepted request must finish with bit-identical
output no matter how many replicas died under it — failover re-serves
from the original prompt, the strict allocator proves zero pages leak
on survivors, and the failover/reroute counters must match the
injected kill count exactly."""
import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.inference import (LLMEngine, ReplicaGone, Router)
from paddle_tpu.models import GPTForCausalLM
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.observability import tracing
from paddle_tpu.resilience import faults


@pytest.fixture(scope="module")
def tiny_gpt():
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _clean():
    faults.clear_all()
    obs.disable()
    obs.reset()
    yield
    faults.clear_all()
    obs.disable()
    obs.reset()


def _factory(model):
    """Same engine shapes as test_llm_engine so the persistent XLA
    cache is warm. Each call builds an INDEPENDENT engine (own pool,
    own executable cache) sharing the read-only weights."""
    def make(_i):
        return LLMEngine(model, max_batch=2, block_size=16,
                         decode_chunk=4, prompt_quantum=16,
                         max_model_len=64)
    return make


def _prompts(n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1024, (k,)).astype(np.int32)
            for k in (5, 9, 13, 21)[:n]]


def _assert_no_leaks(router):
    """Every surviving replica's pool fully reconciles: free + parked
    (LRU) pages == all blocks but the leased trash page."""
    for h in router.replicas:
        if h.engine is None:
            continue
        cache = h.engine.cache
        assert cache.available_blocks == \
            cache.allocator.num_blocks - 1, h.name


def _serve(router, prompts, n_new, rid_prefix=""):
    for i, p in enumerate(prompts):
        router.submit(f"{rid_prefix}{i}", p, max_new_tokens=n_new)
    done = {}
    while router.has_unfinished:
        for r in router.step():
            done[r.request_id] = r
    return done


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------
class TestRouting:
    def test_matches_single_engine(self, tiny_gpt):
        prompts = _prompts()
        router = Router(_factory(tiny_gpt), n_replicas=2)
        done = _serve(router, prompts, 8)
        single = _factory(tiny_gpt)(0).generate(prompts,
                                                max_new_tokens=8)
        for i, s in enumerate(single):
            r = done[f"{i}"]
            assert r.ok
            np.testing.assert_array_equal(r.output_ids, s.output_ids)
        # both replicas actually served (least-loaded distribution)
        assert all(h.engine.stats["prefills"] > 0
                   for h in router.replicas)
        _assert_no_leaks(router)

    def test_affinity_routes_to_prefix_holder(self, tiny_gpt):
        rng = np.random.default_rng(7)
        prefix = rng.integers(0, 1024, (32,)).astype(np.int32)
        turn = [np.concatenate([prefix, rng.integers(
            0, 1024, (k,)).astype(np.int32)]) for k in (3, 5, 7)]
        router = Router(_factory(tiny_gpt), n_replicas=2)
        router.submit("t0", turn[0], max_new_tokens=4, session_id="s")
        owner = router._owner["t0"].name
        while router.has_unfinished:
            router.step()
        # later turns share the 32-token (2-page) prefix: the peek
        # finds it parked on the owner and routes there
        for j, p in enumerate(turn[1:], 1):
            router.submit(f"t{j}", p, max_new_tokens=4,
                          session_id="s")
            assert router._owner[f"t{j}"].name == owner
            while router.has_unfinished:
                router.step()
        assert router.stats["affinity_hit_tokens"] >= 64
        eng = next(h.engine for h in router.replicas
                   if h.name == owner)
        assert eng.stats["prefix_cache_hit_tokens"] >= 64

    def test_affinity_off_is_least_loaded(self, tiny_gpt):
        rng = np.random.default_rng(8)
        prefix = rng.integers(0, 1024, (32,)).astype(np.int32)
        prompts = [np.concatenate([prefix, rng.integers(
            0, 1024, (k,)).astype(np.int32)]) for k in (3, 5)]
        router = Router(_factory(tiny_gpt), n_replicas=2,
                        affinity=False)
        for i, p in enumerate(prompts):
            router.submit(i, p, max_new_tokens=4)
        owners = {router._owner[i].name for i in range(2)}
        assert len(owners) == 2         # blind spread, no clustering
        assert router.stats["affinity_hit_tokens"] == 0
        while router.has_unfinished:
            router.step()

    def test_affinity_headroom_spreads_load(self, tiny_gpt):
        """Affinity must not concentrate a hot prefix onto one replica
        past the headroom factor: once the cached replica's inflight
        blows `affinity_max_inflight_factor` x the least-loaded's, the
        pick falls back to least-loaded (the PR 19 traffic-harness
        gotcha — session affinity erases fleet pipelining)."""
        rng = np.random.default_rng(9)
        prefix = rng.integers(0, 1024, (32,)).astype(np.int32)
        turns = [np.concatenate([prefix, rng.integers(
            0, 1024, (k,)).astype(np.int32)]) for k in (3, 5, 7, 9)]
        router = Router(_factory(tiny_gpt), n_replicas=2,
                        affinity_max_inflight_factor=1.0)
        # seed the prefix on one replica, drained to idle
        router.submit("seed", turns[0], max_new_tokens=4)
        owner = router._owner["seed"].name
        while router.has_unfinished:
            router.step()
        # pile up same-prefix admissions WITHOUT stepping: affinity
        # wants the owner every time, but at factor 1.0 the owner may
        # never carry more inflight than the idle replica + 1 — the
        # overflow spreads
        for j, p in enumerate(turns):
            router.submit(f"q{j}", p, max_new_tokens=4)
        owners = [router._owner[f"q{j}"].name
                  for j in range(len(turns))]
        assert owners.count(owner) == 2
        assert len(set(owners)) == 2        # both replicas carry load
        while router.has_unfinished:
            router.step()
        _assert_no_leaks(router)

    def test_affinity_headroom_none_always_honors_cache(self,
                                                        tiny_gpt):
        """factor=None pins the old behavior: affinity wins no matter
        how lopsided the load gets."""
        rng = np.random.default_rng(9)
        prefix = rng.integers(0, 1024, (32,)).astype(np.int32)
        turns = [np.concatenate([prefix, rng.integers(
            0, 1024, (k,)).astype(np.int32)]) for k in (3, 5, 7, 9)]
        router = Router(_factory(tiny_gpt), n_replicas=2,
                        affinity_max_inflight_factor=None)
        router.submit("seed", turns[0], max_new_tokens=4)
        owner = router._owner["seed"].name
        while router.has_unfinished:
            router.step()
        for j, p in enumerate(turns):
            router.submit(f"q{j}", p, max_new_tokens=4)
        owners = {router._owner[f"q{j}"].name
                  for j in range(len(turns))}
        assert owners == {owner}        # all piled onto the holder
        while router.has_unfinished:
            router.step()
        _assert_no_leaks(router)

    def test_duplicate_rid_refused(self, tiny_gpt):
        router = Router(_factory(tiny_gpt), n_replicas=2)
        router.submit("a", _prompts(1)[0], max_new_tokens=4)
        with pytest.raises(ValueError):
            router.submit("a", _prompts(1)[0], max_new_tokens=4)
        while router.has_unfinished:
            router.step()


# ---------------------------------------------------------------------------
# chaos: kill a replica mid-stream, three ways
# ---------------------------------------------------------------------------
class TestChaosFailover:
    def _chaos_run(self, model, spec_kw, router_kw=None,
                   warm=False):
        """Start 4 requests on 2 replicas, step once so everything is
        mid-stream, kill replica-0 via the named fault point, run to
        completion. Returns (router, {rid: result})."""
        prompts = _prompts()
        router = Router(_factory(model), n_replicas=2,
                        **(router_kw or {}))
        if warm:                # compile every bucket first
            for r in _serve(router, prompts, 12, "w").values():
                assert r.ok
        for i, p in enumerate(prompts):
            router.submit(i, p, max_new_tokens=12)
        router.step()           # prefills done, decodes in flight
        victims = len(router.replicas.handles[0].inflight)
        assert victims > 0      # the kill really is mid-stream
        done = {}
        with faults.inject("router.replica.step",
                           match={"replica": "replica-0"}, times=1,
                           **spec_kw):
            while router.has_unfinished:
                for r in router.step():
                    done[r.request_id] = r
        return router, done, victims

    def _assert_bit_identical(self, model, done):
        single = _factory(model)(0).generate(_prompts(),
                                             max_new_tokens=12)
        for i, s in enumerate(single):
            assert done[i].ok, (i, done[i].finish_reason,
                                done[i].error)
            np.testing.assert_array_equal(done[i].output_ids,
                                          s.output_ids)

    def test_exception_kill(self, tiny_gpt):
        obs.enable()
        router, done, victims = self._chaos_run(
            tiny_gpt, dict(exc=RuntimeError("chaos: step blew up")))
        self._assert_bit_identical(tiny_gpt, done)
        _assert_no_leaks(router)
        assert router.stats["failovers"] == 1       # == injected kills
        assert router.stats["reroutes"] == victims
        # zero-valued rows are label sets other tests registered
        # before obs.reset() (reset zeroes values but keeps series)
        assert {k: v for k, v in _series(
            "paddle_tpu_router_failovers_total").items() if v} == \
            {("exception",): 1}
        rr = sum(_series("paddle_tpu_router_reroutes_total").values())
        assert rr == victims

    def test_hard_exit_kill(self, tiny_gpt):
        """ReplicaGone — the in-process stand-in for a hard process
        exit: the engine object is discarded unasked (no cleanup ran),
        and reintegration must build a FRESH engine."""
        router, done, victims = self._chaos_run(
            tiny_gpt, dict(exc=ReplicaGone("chaos: SIGKILL")),
            router_kw=dict(cooldown_s=3600.0))
        self._assert_bit_identical(tiny_gpt, done)
        _assert_no_leaks(router)
        h0 = router.replicas.handles[0]
        assert h0.state == "dead" and h0.engine is None
        assert router.stats["failovers"] == 1
        assert router.stats["reroutes"] == victims

    def test_hang_past_timeout(self, tiny_gpt):
        """A step that completes but blows unhealthy_step_s: the
        replica is quarantined ALIVE — in-flight requests drain
        through abort_request (pages reclaimed on the spot) and the
        warm engine is kept for reintegration."""
        router, done, victims = self._chaos_run(
            tiny_gpt, dict(delay=1.5),
            router_kw=dict(unhealthy_step_s=1.0, cooldown_s=3600.0),
            warm=True)
        for k in list(done):        # drop the warmup requests
            if isinstance(k, str) and k.startswith("w"):
                del done[k]
        self._assert_bit_identical(tiny_gpt, done)
        h0 = router.replicas.handles[0]
        assert h0.state == "dead" and h0.engine is not None
        assert h0.engine.stats["aborted_requests"] == victims
        assert router.stats["failovers"] == 1
        assert router.stats["reroutes"] == victims
        _assert_no_leaks(router)    # incl. the drained quarantined one

    def test_no_cross_request_poisoning(self, tiny_gpt):
        """A poisoned REQUEST is not a poisoned REPLICA: the engine's
        per-sequence isolation fails it alone, the router keeps the
        replica, and every neighbor (same replica included) stays
        oracle-exact."""
        prompts = _prompts()
        router = Router(_factory(tiny_gpt), n_replicas=2)
        for i, p in enumerate(prompts):
            router.submit(i, p, max_new_tokens=8)
        bad = 0
        victim_replica = router._owner[bad].name
        with faults.inject("engine.decode.seq",
                           exc=RuntimeError("poison"),
                           match={"rid": bad}):
            done = {}
            while router.has_unfinished:
                for r in router.step():
                    done[r.request_id] = r
        assert done[bad].finish_reason == "error"
        assert router.stats["failovers"] == 0
        assert all(h.live for h in router.replicas)
        single = _factory(tiny_gpt)(0).generate(prompts,
                                                max_new_tokens=8)
        for i, s in enumerate(single):
            if i == bad:
                continue
            np.testing.assert_array_equal(done[i].output_ids,
                                          s.output_ids)
        assert router._owner == {}
        _assert_no_leaks(router)
        assert victim_replica   # (documented: the replica survived)

    def test_trace_tree_stays_connected(self, tiny_gpt):
        """Failover keeps ONE trace per request: the re-served
        attempt's engine events and the router.reroute marker all
        carry the original trace_id, and the terminal root span is
        anchored at the ORIGINAL enqueue."""
        obs.enable()
        prompts = _prompts()
        router = Router(_factory(tiny_gpt), n_replicas=2)
        for i, p in enumerate(prompts):
            router.submit(i, p, max_new_tokens=12)
        router.step()
        victims = [r.rid for r in
                   router.replicas.handles[0].inflight.values()]
        with faults.inject("router.replica.step",
                           exc=ReplicaGone("chaos"),
                           match={"replica": "replica-0"}, times=1):
            while router.has_unfinished:
                router.step()
        evs = tracing.events()
        rid = victims[0]
        roots = [e for e in evs if e["name"] == "request"
                 and e.get("args", {}).get("request_id") == str(rid)]
        assert len(roots) == 1          # ONE terminal root span
        tid = roots[0]["trace_id"]
        reroutes = [e for e in evs if e["name"] == "router.reroute"
                    and e.get("args", {}).get("request_id") == str(rid)]
        assert reroutes and all(e["trace_id"] == tid
                                for e in reroutes)
        prefills = [e for e in evs if e["name"] == "request.prefill"
                    and e.get("args", {}).get("request_id") == str(rid)]
        # prefilled on the doomed replica AND re-prefilled on the
        # survivor — same tree
        assert len(prefills) >= 2
        assert all(e["trace_id"] == tid for e in prefills)


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------
class TestCircuitBreaker:
    def test_backoff_doubles_and_reintegrates_fresh(self, tiny_gpt):
        router = Router(_factory(tiny_gpt), n_replicas=2,
                        cooldown_s=10.0, cooldown_factor=2.0,
                        max_cooldown_s=25.0, probation_steps=1)
        clock = [1000.0]
        router._now = lambda: clock[0]
        h0 = router.replicas.handles[0]
        old_engine = h0.engine

        def kill_and_drain(n_new, tag):
            for i, p in enumerate(_prompts(2)):
                router.submit(f"{tag}{i}", p, max_new_tokens=n_new)
            with faults.inject("router.replica.step",
                               exc=ReplicaGone("chaos"),
                               match={"replica": "replica-0"},
                               times=1):
                while router.has_unfinished:
                    router.step()

        kill_and_drain(4, "a")
        assert h0.state == "dead" and h0.cooldown_s == 10.0
        # breaker open: new traffic routes around the dead replica
        router.submit("solo", _prompts(1)[0], max_new_tokens=4)
        assert router._owner["solo"].name == "replica-1"
        while router.has_unfinished:
            router.step()
        # cooldown elapses -> probation with a FRESH engine
        clock[0] += 10.5
        router.step()
        assert h0.state == "probation"
        assert h0.engine is not None and h0.engine is not old_engine
        # failure during probation re-trips at DOUBLED backoff
        kill_and_drain(4, "b")
        assert h0.state == "dead" and h0.cooldown_s == 20.0
        clock[0] += 20.5
        router.step()
        # a third trip is bounded by max_cooldown_s
        kill_and_drain(4, "c")
        assert h0.cooldown_s == 25.0
        clock[0] += 25.5
        router.step()                   # reintegrate -> probation
        assert h0.state == "probation"
        # clean probation step(s) restore healthy and RESET backoff
        done = _serve(router, _prompts(2, seed=99), 4, "d")
        assert all(r.ok for r in done.values())
        assert h0.state == "healthy" and h0.cooldown_s == 0.0
        assert router.stats["failovers"] == 3

    def test_idle_probation_burns_down(self, tiny_gpt):
        """A reintegrated replica that gets no traffic still finishes
        probation (it cannot fail while idle) — otherwise an unrelated
        failure hours later reads as a consecutive trip and doubles
        the backoff."""
        router = Router(_factory(tiny_gpt), n_replicas=2,
                        cooldown_s=5.0, probation_steps=2)
        clock = [0.0]
        router._now = lambda: clock[0]
        h0 = router.replicas.handles[0]
        for i, p in enumerate(_prompts(2)):
            router.submit(i, p, max_new_tokens=4)
        with faults.inject("router.replica.step",
                           exc=ReplicaGone("chaos"),
                           match={"replica": "replica-0"}, times=1):
            while router.has_unfinished:
                router.step()
        assert h0.state == "dead"
        clock[0] += 5.5
        router.step()                   # reintegrates; observe-only
        assert h0.state == "probation"
        router.step()                   # idle pass 1
        router.step()                   # idle pass 2 -> healthy
        assert h0.state == "healthy" and h0.cooldown_s == 0.0

    def test_shedding_when_capacity_drops(self, tiny_gpt):
        """Losing a replica halves capacity: the router degrades by
        shedding new admissions (finish_reason="rejected", reason on
        .error) instead of queue-collapsing onto the survivor —
        everything it DID accept still finishes."""
        router = Router(_factory(tiny_gpt), n_replicas=2,
                        max_inflight=2, cooldown_s=3600.0)
        prompts = _prompts()
        done = {}

        def pump(n=1):
            for _ in range(n):
                for r in router.step():
                    done[r.request_id] = r

        for i, p in enumerate(prompts):
            router.submit(i, p, max_new_tokens=8)     # 4 <= 2*2: all in
        pump()
        with faults.inject("router.replica.step",
                           exc=ReplicaGone("chaos"),
                           match={"replica": "replica-0"}, times=1):
            pump()
        assert len(router.replicas.live()) == 1
        # the survivor's cap is now 2: anything beyond it sheds
        # instead of queueing
        for j in range(3):
            router.submit(f"x{j}", prompts[0], max_new_tokens=8)
        while router.has_unfinished:
            pump()
        shed = [r for r in done.values()
                if r.finish_reason == "rejected"]
        assert shed and all("capacity" in r.error for r in shed)
        single = _factory(tiny_gpt)(0).generate(prompts,
                                                max_new_tokens=8)
        for i, s in enumerate(single):      # accepted ones finished
            np.testing.assert_array_equal(done[i].output_ids,
                                          s.output_ids)
        _assert_no_leaks(router)


# ---------------------------------------------------------------------------
# engine abort hook (the drain primitive the router builds on)
# ---------------------------------------------------------------------------
class TestAbortRequest:
    def test_abort_mid_decode_frees_everything(self, tiny_gpt):
        eng = _factory(tiny_gpt)(0)
        prompts = _prompts(2)
        for i, p in enumerate(prompts):
            eng.add_request(i, p, max_new_tokens=16)
        eng.step()                      # both mid-decode
        assert eng.abort_request(0)
        (r,) = [r for r in eng.step() if r.request_id == 0]
        assert r.finish_reason == "aborted" and not r.ok
        assert len(r.output_ids) >= 1   # kept what it had
        assert eng.stats["aborted_requests"] == 1
        # neighbor unaffected, oracle-exact
        done = {}
        while eng.has_unfinished:
            for rr in eng.step():
                done[rr.request_id] = rr
        single = _factory(tiny_gpt)(0).generate(prompts,
                                                max_new_tokens=16)
        np.testing.assert_array_equal(done[1].output_ids,
                                      single[1].output_ids)
        # strict allocator: every page back in circulation (shareable
        # prefix blocks parked, the rest freed)
        assert eng.cache.available_blocks == \
            eng.cache.allocator.num_blocks - 1

    def test_abort_queued_before_prefill(self, tiny_gpt):
        eng = _factory(tiny_gpt)(0)
        free0 = eng.cache.allocator.num_free
        eng.add_request("q", _prompts(1)[0], max_new_tokens=8)
        assert eng.abort_request("q")
        assert eng.cache.allocator.num_free == free0    # never leased
        (r,) = eng.step()
        assert r.finish_reason == "aborted"
        assert len(r.output_ids) == 0
        assert not eng.has_unfinished

    def test_abort_unknown_rid(self, tiny_gpt):
        eng = _factory(tiny_gpt)(0)
        assert eng.abort_request("ghost") is False

    def test_abort_racing_failover_never_resurrects(self, tiny_gpt):
        """router.abort() then the replica dies before the aborted
        result surfaced: the cancellation must win — failover must NOT
        re-serve the request and hand the caller a completed result."""
        router = Router(_factory(tiny_gpt), n_replicas=2,
                        cooldown_s=3600.0)
        for i, p in enumerate(_prompts(2)):
            router.submit(i, p, max_new_tokens=16)
        router.step()
        h = router._owner[0]
        assert router.abort(0)
        with faults.inject("router.replica.step",
                           exc=ReplicaGone("chaos"),
                           match={"replica": h.name}, times=1):
            done = {}
            while router.has_unfinished:
                for r in router.step():
                    done[r.request_id] = r
        assert done[0].finish_reason == "aborted"
        assert router.stats["reroutes"] <= 1    # never request 0
        assert done[1].ok
        _assert_no_leaks(router)

    def test_infeasible_request_sheds(self, tiny_gpt):
        """An over-model-len request can fit NO replica: the engine's
        admission raises and the router converts it to a shed."""
        obs.enable()
        router = Router(_factory(tiny_gpt), n_replicas=2)
        router.submit("big", np.zeros(100, np.int32),
                      max_new_tokens=10)
        (r,) = router.step()
        assert r.finish_reason == "rejected"
        assert "infeasible" in r.error
        assert _series("paddle_tpu_router_shed_total")[
            ("infeasible",)] == 1
        assert not router.has_unfinished

    def test_router_abort_delivers_result(self, tiny_gpt):
        router = Router(_factory(tiny_gpt), n_replicas=2)
        for i, p in enumerate(_prompts(2)):
            router.submit(i, p, max_new_tokens=16)
        router.step()
        assert router.abort(0)
        done = {}
        while router.has_unfinished:
            for r in router.step():
                done[r.request_id] = r
        assert done[0].finish_reason == "aborted"
        assert done[1].ok
        _assert_no_leaks(router)


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------
def _series(name):
    return obs.snapshot()[name]["series"]


class TestRouterObservability:
    def test_replica_gauges_and_shed_counter(self, tiny_gpt):
        obs.enable()
        router = Router(_factory(tiny_gpt), n_replicas=2,
                        max_inflight=1, cooldown_s=3600.0)
        prompts = _prompts()
        router.submit(0, prompts[0], max_new_tokens=4)
        router.submit(1, prompts[1], max_new_tokens=4)
        router.submit(2, prompts[2], max_new_tokens=4)  # over cap
        done = {}
        while router.has_unfinished:
            for r in router.step():
                done[r.request_id] = r
        assert done[2].finish_reason == "rejected"
        shed = _series("paddle_tpu_router_shed_total")
        assert shed[("capacity",)] == 1
        state = _series("paddle_tpu_router_replica_state")
        assert state[("replica-0", "healthy")] == 1.0
        assert state[("replica-0", "dead")] == 0.0
        infl = _series("paddle_tpu_router_replica_inflight")
        assert infl[("replica-0",)] == 0.0
        fin = _series("paddle_tpu_request_finished_total")
        assert fin[("rejected",)] == 1
        assert fin[("length",)] == 2

    def test_disabled_mode_no_allocation_growth(self, tiny_gpt):
        """The standing acceptance guard, extended over the router's
        hot observability paths: gauge updates and idle scheduling
        passes are a flag check when obs is off."""
        import tracemalloc
        router = Router(_factory(tiny_gpt), n_replicas=2)
        assert not obs.enabled()
        def burst(n):
            for _ in range(n):
                router._update_gauges()
                router.step()
        # the interpreter retains a constant ~2KB of per-call-path
        # caches regardless of iteration count, so the guard compares
        # two windows of the SAME call site: a real per-op allocation
        # scales with n and shows up as the difference, the constant
        # residual cancels
        tracemalloc.start()
        burst(64)
        grown = []
        for n in (1000, 4000):
            base = tracemalloc.get_traced_memory()[0]
            burst(n)
            grown.append(tracemalloc.get_traced_memory()[0] - base)
        tracemalloc.stop()
        assert grown[1] - grown[0] < 2048, \
            f"disabled-mode router ops allocate per step: {grown}"
        assert tracing.events() == []


# ---------------------------------------------------------------------------
# obs_top replicas panel
# ---------------------------------------------------------------------------
class TestObsTopReplicasPanel:
    def _obs_top(self):
        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        sys.path.insert(0, tools)
        try:
            import obs_top
        finally:
            sys.path.remove(tools)
        return obs_top

    def test_renders_states_and_totals(self, tiny_gpt):
        obs_top = self._obs_top()
        obs.enable()
        router = Router(_factory(tiny_gpt), n_replicas=2,
                        cooldown_s=3600.0)
        for i, p in enumerate(_prompts(2)):
            router.submit(i, p, max_new_tokens=16)
        router.step()
        assert router.replicas.handles[1].inflight  # kill is mid-stream
        with faults.inject("router.replica.step",
                           exc=ReplicaGone("chaos"),
                           match={"replica": "replica-1"}, times=1):
            while router.has_unfinished:
                router.step()
        frame = obs_top.render(json.loads(obs.to_json()))
        assert "== replicas ==" in frame
        assert "replica-0" in frame and "healthy" in frame
        assert "replica-1" in frame and "dead" in frame
        assert "failovers=1" in frame
        line = [ln for ln in frame.splitlines()
                if "reroutes=" in ln][0]
        assert "shed" not in line or "shed:" in frame


# ---------------------------------------------------------------------------
# tools/known_failures.py — machine-checkable "no NEW failures"
# ---------------------------------------------------------------------------
class TestKnownFailures:
    def _tool(self):
        from tools import known_failures
        return known_failures

    def test_clean_log_passes(self, tmp_path):
        kf = self._tool()
        log = tmp_path / "t1.log"
        log.write_text("....\n10 passed in 1.0s\n")
        report = kf.check_log(str(log))
        assert report.new == [] and report.ok

    def test_known_failures_tolerated_new_flagged(self, tmp_path):
        kf = self._tool()
        # the checked-in list is empty on the installed jax: the
        # mechanism is exercised against a manifest of the test's own
        known = "tests/test_env.py::test_needs_other_box"
        manifest = tmp_path / "manifest.json"
        manifest.write_text(json.dumps(
            {"failures": [known], "flaky": []}))
        log = tmp_path / "t1.log"
        log.write_text(
            f"FAILED {known} - AttributeError: shard_map\n"
            "FAILED tests/test_new.py::test_regression - boom\n"
            f"FAILED {known} - AttributeError: shard_map\n"
            "2 failed, 1 passed in 2.0s\n")
        report = kf.check_log(str(log), str(manifest))
        assert report.new == ["tests/test_new.py::test_regression"]
        assert not report.ok
        assert known in report.known_seen

    def test_flaky_failures_reported_not_fatal(self, tmp_path):
        kf = self._tool()
        # the checked-in list is empty (no test reads a clock): a
        # manifest of the test's own, as for the failures
        flaky = "tests/test_env.py::test_ranks_two_wall_clocks"
        manifest = tmp_path / "manifest.json"
        manifest.write_text(json.dumps(
            {"failures": [], "flaky": [flaky]}))
        log = tmp_path / "t1.log"
        log.write_text(f"FAILED {flaky} - timing\n1 failed\n")
        report = kf.check_log(str(log), str(manifest))
        assert report.ok and report.flaky_seen == [flaky]

    def test_manifest_matches_checked_in_baseline(self):
        """The manifest is the machine-readable copy of the
        environment-failure list the repo docs cite — pin its shape
        so a drive-by edit can't silently blank the gate."""
        m = self._tool().load_manifest()
        # every environment failure the seed listed passes on the
        # installed jax 0.9.0: a new entry needs its reason in _comment
        assert m["failures"] == []
        # and no test ranks or bounds a wall clock
        assert m["flaky"] == []
