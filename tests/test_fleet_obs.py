"""Fleet observability plane (paddle_tpu/observability/fleet.py):
snapshot-delta encoding, sequence-numbered shipping with rollback +
dedupe, aggregator health/staleness, capacity records, the
obs_top fleet panel, the disabled-mode overhead guard — and the real
spawn boundary: N worker processes shipping metrics + spans to an
aggregator over the HMAC RPC layer, one killed -9 mid-run.

Module-level imports stay light: spawned children re-import this
module (spawn start method), and heavyweight imports belong inside
the functions that run after the JAX_PLATFORMS=cpu env guard."""
import json
import multiprocessing
import os
import signal
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fleet_clean():
    """Every test starts disabled with empty stores, a neutral fleet
    identity, and no aggregator serving in this process."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import fleet, tracing
    obs.disable()
    obs.reset()
    tracing.clear()
    cap = tracing.capacity()
    saved = (fleet._PROCESS, fleet._ROLE, fleet._ROLE_EXPLICIT)
    fleet._PROCESS, fleet._ROLE, fleet._ROLE_EXPLICIT = None, None, False
    yield
    if fleet._AGGREGATOR is not None:
        fleet._AGGREGATOR.close()
    fleet._PROCESS, fleet._ROLE, fleet._ROLE_EXPLICIT = saved
    obs.disable()
    obs.reset()
    tracing.set_capacity(cap)


def _snap_series(reg, name):
    return reg.snapshot()[name]["series"]


# ---------------------------------------------------------------------------
# delta encoding (the one wire format)
# ---------------------------------------------------------------------------
class TestDeltaSnapshot:
    def _regs(self):
        from paddle_tpu.observability import MetricsRegistry
        return MetricsRegistry(), MetricsRegistry()

    def test_counter_and_gauge_deltas_telescope(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet
        obs.enable()
        src, dst = self._regs()
        c = src.counter("t_fd_total", "", ("k",)).labels(k="a")
        g = src.gauge("t_fd_gauge", "")
        c.inc(3)
        g.set(10.0)
        base = None
        for expect_c, expect_g in ((3.0, 10.0), (5.0, 4.0)):
            cur = src.snapshot()
            dst.merge(fleet.delta_snapshot(cur, base))
            base = cur
            assert _snap_series(dst, "t_fd_total")[("a",)] == expect_c
            assert _snap_series(dst, "t_fd_gauge")[()] == expect_g
            if expect_c == 3.0:     # second round: inc + gauge DOWN
                c.inc(2)
                g.set(4.0)

    def test_zero_delta_series_pruned(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet
        obs.enable()
        src, _ = self._regs()
        c = src.counter("t_fdp_total", "")
        h = src.histogram("t_fdp_seconds", "")
        c.inc()
        h.observe(0.1)
        cur = src.snapshot()
        assert fleet.delta_snapshot(cur, cur) == {}
        full = fleet.delta_snapshot(cur, None)
        assert set(full) == {"t_fdp_total", "t_fdp_seconds"}

    def test_histogram_delta_buckets_subtract(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet
        obs.enable()
        src, dst = self._regs()
        h = src.histogram("t_fdh_seconds", "", buckets=(0.1, 1.0))
        h.observe(0.05)
        base = src.snapshot()
        h.observe(0.5)
        h.observe(2.0)
        delta = fleet.delta_snapshot(src.snapshot(), base)
        val = delta["t_fdh_seconds"]["series"][()]
        assert val["buckets"] == [0, 1, 1] and val["count"] == 2
        dst.merge(delta)
        out = _snap_series(dst, "t_fdh_seconds")[()]
        assert out["count"] == 2 and out["sum"] == pytest.approx(2.5)

    def test_reset_peer_recontributes_in_full(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet
        obs.enable()
        src, _ = self._regs()
        c = src.counter("t_fdr_total", "")
        c.inc(9)
        base = src.snapshot()
        src.reset()
        c.inc(2)                    # restarted accounting
        delta = fleet.delta_snapshot(src.snapshot(), base)
        assert delta["t_fdr_total"]["series"][()] == 2.0

    def test_histogram_reset_hidden_by_regrown_count_ships_full(self):
        """A peer that resets and then observes PAST its old total
        count must still be detected (per-bucket backwards movement) —
        otherwise negative bucket deltas would merge into the fleet
        registry."""
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet
        obs.enable()
        src, dst = self._regs()
        h = src.histogram("t_fdrh_seconds", "", buckets=(0.1, 1.0))
        for _ in range(5):
            h.observe(0.05)         # 5 in bucket 0
        base = src.snapshot()
        src.reset()
        for _ in range(7):
            h.observe(0.5)          # regrown past the old count
        delta = fleet.delta_snapshot(src.snapshot(), base)
        val = delta["t_fdrh_seconds"]["series"][()]
        assert val["buckets"] == [0, 7, 0] and val["count"] == 7
        dst.merge(delta)
        out = _snap_series(dst, "t_fdrh_seconds")[()]
        assert out["count"] == 7 and min(out["buckets"]) >= 0

    def test_worker_farewell_merges_through_one_path(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet, tracing
        obs.enable()
        obs.registry().counter("t_fw_total", "").inc(3)
        tracing.add_event("t.fw", 1.0, 2.0)
        wf = fleet.worker_farewell()
        assert wf["v"] == fleet.BUNDLE_VERSION and wf["seq"] == 1
        obs.reset()
        fleet.merge_bundle_local(wf)
        assert obs.snapshot()["t_fw_total"]["series"][()] == 3
        assert any(e["name"] == "t.fw" for e in tracing.events())
        # legacy {"metrics","trace"} farewell shape still merges
        obs.reset()
        fleet.merge_bundle_local({"metrics": wf["metrics"],
                                  "trace": wf["trace"]})
        assert obs.snapshot()["t_fw_total"]["series"][()] == 3


# ---------------------------------------------------------------------------
# aggregator semantics (direct ingest — no sockets)
# ---------------------------------------------------------------------------
class TestAggregator:
    def _agg(self, stale_after_s=10.0):
        from paddle_tpu.observability.fleet import FleetAggregator
        return FleetAggregator(stale_after_s=stale_after_s)

    def _bundle(self, proc, seq, series=None, role="replica"):
        from paddle_tpu.observability import MetricsRegistry, fleet
        md = None
        if series is not None:
            src = MetricsRegistry()
            from paddle_tpu.observability import metrics as _m
            _m.enable()
            for name, v in series.items():
                src.counter(name, "test").inc(v)
            md = fleet.delta_snapshot(src.snapshot(), None)
        return fleet.make_bundle(proc, role, seq, metrics_delta=md)

    def test_process_label_dimension(self):
        agg = self._agg()
        agg.ingest(self._bundle("pa", 1, {"t_fa_total": 3}))
        agg.ingest(self._bundle("pb", 1, {"t_fa_total": 5}))
        s = _snap_series(agg.registry, "t_fa_total")
        assert s[("pa",)] == 3 and s[("pb",)] == 5
        expo = agg.to_prometheus()
        assert 'process="pa"' in expo and 'process="pb"' in expo

    def test_seq_dedupe_no_double_count(self):
        agg = self._agg()
        b = self._bundle("pa", 1, {"t_fs_total": 4})
        assert agg.ingest(b)["ok"]
        ack = agg.ingest(b)          # redelivery after a lost ack
        assert ack["duplicate"] and ack["last_seq"] == 1
        stale = self._bundle("pa", 1, {"t_fs_total": 100})
        assert agg.ingest(stale)["duplicate"]
        assert _snap_series(agg.registry, "t_fs_total")[("pa",)] == 4
        assert _snap_series(
            agg.registry,
            "paddle_tpu_fleet_duplicate_bundles_total")[("pa",)] == 2

    def test_schema_skew_quarantined_not_poisoning(self):
        from paddle_tpu.observability import MetricsRegistry, fleet
        from paddle_tpu.observability import metrics as _m
        _m.enable()
        agg = self._agg()
        a = MetricsRegistry()
        a.histogram("t_fq_seconds", "", buckets=(0.1,)).observe(0.05)
        agg.ingest(fleet.make_bundle(
            "pa", "r", 1,
            metrics_delta=fleet.delta_snapshot(a.snapshot(), None)))
        b = MetricsRegistry()
        b.histogram("t_fq_seconds", "", buckets=(9.0,)).observe(1.0)
        agg.ingest(fleet.make_bundle(
            "pb", "r", 1,
            metrics_delta=fleet.delta_snapshot(b.snapshot(), None)))
        snap = agg.registry.snapshot()
        assert snap["t_fq_seconds"]["series"][("pa",)]["count"] == 1
        assert snap["t_fq_skew_seconds"]["series"][("pb",)]["count"] == 1
        assert snap["paddle_tpu_fleet_quarantined_series_total"][
            "series"][("pb",)] == 1

    def test_poison_bundle_rejected_with_accounting_seq_advances(self):
        """Three peers, three schemas for one name: the third cannot
        merge even under quarantine (slot taken by the second). Its
        metric delta is dropped WITH accounting and the seq still
        advances — the agent must not be wedged into redelivering a
        poison bundle forever, and a redelivery must dedupe instead of
        partially re-merging."""
        from paddle_tpu.observability import MetricsRegistry, fleet
        from paddle_tpu.observability import metrics as _m
        _m.enable()
        agg = self._agg()

        def hist_bundle(proc, bucket):
            r = MetricsRegistry()
            r.histogram("t_fp_seconds", "", buckets=(bucket,)) \
                .observe(bucket / 2)
            return fleet.make_bundle(
                proc, "r", 1,
                metrics_delta=fleet.delta_snapshot(r.snapshot(), None))

        assert not agg.ingest(hist_bundle("pa", 0.1))["rejected_metrics"]
        assert not agg.ingest(hist_bundle("pb", 1.0))["rejected_metrics"]
        poison = hist_bundle("pc", 5.0)
        ack = agg.ingest(poison)
        assert ack["ok"] and ack["rejected_metrics"]
        assert agg.processes()["pc"]["last_seq"] == 1
        assert agg.ingest(poison)["duplicate"]   # redelivery dedupes
        snap = agg.registry.snapshot()
        assert snap["t_fp_seconds"]["series"][("pa",)]["count"] == 1
        assert snap["t_fp_skew_seconds"]["series"][("pb",)]["count"] == 1
        assert ("pc",) not in snap["t_fp_seconds"]["series"]
        assert snap["paddle_tpu_fleet_rejected_bundles_total"][
            "series"][("pc",)] == 1

    def test_heartbeat_staleness(self):
        agg = self._agg(stale_after_s=2.0)
        agg.ingest(self._bundle("pa", 1))
        h = agg.health()
        assert h["pa"]["up"] and h["pa"]["age_s"] < 2.0
        h = agg.health(now=time.time() + 5.0)
        assert not h["pa"]["up"]
        assert _snap_series(
            agg.registry,
            "paddle_tpu_fleet_process_up")[("pa",)] == 0.0
        assert _snap_series(
            agg.registry,
            "paddle_tpu_fleet_heartbeat_age_seconds")[("pa",)] > 2.0

    def test_respawned_process_resets_seq_epoch(self):
        """Crash-restart under a reused process name: the new
        incarnation's agent restarts seq at 1 with a new pid — the
        aggregator must open a new epoch instead of deduping the live
        process into staleness. Merged totals keep both lives'
        history; capacity re-baselines."""
        from paddle_tpu.observability import fleet
        agg = self._agg(stale_after_s=60.0)

        def bundle(seq, pid, n):
            b = self._bundle("pr", seq, {"t_rs_total": n})
            b["heartbeat"]["pid"] = pid
            return b

        agg.ingest(bundle(1, 100, 4))
        agg.ingest(bundle(2, 100, 3))
        assert agg.ingest(bundle(2, 100, 9))["duplicate"]  # same life
        # respawn: same name, new pid, seq restarts at 1
        ack = agg.ingest(bundle(1, 200, 5))
        assert ack["ok"] and not ack.get("duplicate")
        assert agg.processes()["pr"]["last_seq"] == 1
        assert agg.processes()["pr"]["pid"] == 200
        assert _snap_series(agg.registry, "t_rs_total")[("pr",)] == 12
        assert _snap_series(
            agg.registry,
            "paddle_tpu_fleet_process_restarts_total")[("pr",)] == 1
        assert agg.health()["pr"]["up"]

    def test_merge_unknown_kind_and_malformed_value_are_skew(self):
        """A newer-revision peer's unknown metric kind, and a
        non-numeric series value, must surface as MergeSkewError (the
        aggregator's rejected-bundle path), never as a bare
        KeyError/TypeError mid-mutation."""
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import MetricsRegistry, fleet
        from paddle_tpu.observability import metrics as _m
        _m.enable()
        dst = MetricsRegistry()
        snap = {"t_uk_things": {
            "kind": "summary", "help": "", "labelnames": (),
            "series": {(): 1.0}}}
        with pytest.raises(obs.MergeSkewError, match="unknown metric"):
            dst.merge(snap, on_skew="quarantine")
        bad_val = {"t_bv_total": {
            "kind": "counter", "help": "", "labelnames": (),
            "series": {(): {"not": "a number"}}}}
        with pytest.raises(obs.MergeSkewError, match="not numeric"):
            dst.merge(bad_val)
        # the aggregator converts either into a counted rejection, not
        # a wedge: the seq advances and the agent moves on
        agg = self._agg()
        b = fleet.make_bundle("pu", "r", 1, metrics_delta=snap)
        assert agg.ingest(b)["rejected_metrics"]
        assert agg.processes()["pu"]["last_seq"] == 1

    def test_unknown_bundle_version_rejected(self):
        agg = self._agg()
        with pytest.raises(ValueError, match="fleet bundle"):
            agg.ingest({"v": 99, "process": "pa", "seq": 1})


# ---------------------------------------------------------------------------
# agent shipping over real sockets (agent + aggregator co-located:
# asserts go against the fleet registry, which is feedback-free)
# ---------------------------------------------------------------------------
class TestAgentShipping:
    def test_ship_rollback_and_redelivery(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet
        obs.enable()
        agg = fleet.serve_aggregator()
        c = obs.registry().counter("t_as_total", "")
        agent = fleet.FleetAgent(agg.endpoint, process="p1",
                                 role="replica", interval_s=60.0,
                                 timeout_s=5.0)
        c.inc(5)
        assert agent.ship()
        assert _snap_series(agg.registry, "t_as_total")[("p1",)] == 5
        port = int(agg.endpoint.rsplit(":", 1)[1])
        agg.close()
        c.inc(7)
        assert not agent.ship()     # aggregator gone: rolled back
        assert agent._seq == 1
        fails = obs.snapshot()[
            "paddle_tpu_fleet_agent_ship_failures_total"]["series"][()]
        assert fails == 1
        agg2 = fleet.serve_aggregator(port=port)
        assert agent.ship()         # accumulated delta redelivers
        assert agent._seq == 2
        # the new aggregator sees exactly the un-acknowledged delta
        assert _snap_series(agg2.registry, "t_as_total")[("p1",)] == 7
        agg2.close()

    def test_heartbeat_only_when_disabled(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet
        assert not obs.enabled()
        agg = fleet.serve_aggregator()
        agent = fleet.FleetAgent(agg.endpoint, process="poff",
                                 role="replica", interval_s=60.0)
        assert agent.ship()
        procs = agg.processes()
        assert procs["poff"]["last_seq"] == 1
        # no series shipped: the fleet registry holds only the
        # aggregator's own bookkeeping (fleet health + the cross-rank
        # collective attribution gauges it publishes itself)
        names = set(agg.registry.snapshot())
        assert all(n.startswith("paddle_tpu_fleet_")
                   or n.startswith("paddle_tpu_collective_")
                   for n in names)
        agg.close()

    def test_ring_rotation_drops_are_counted(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet, tracing
        obs.enable()
        tracing.set_capacity(8)
        agg = fleet.serve_aggregator()
        agent = fleet.FleetAgent(agg.endpoint, process="pr",
                                 role="replica", interval_s=60.0)
        for i in range(30):
            tracing.add_event("t.ring_spam", float(i), 1.0)
        assert agent.ship()
        dropped = obs.snapshot()[
            "paddle_tpu_fleet_agent_dropped_events_total"]["series"]
        assert dropped[("ring",)] == 22      # 30 recorded, ring kept 8
        agg.close()

    def test_outbound_buffer_overflow_counted(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet, tracing
        obs.enable()
        agent = fleet.FleetAgent("127.0.0.1:1", process="pb",
                                 role="replica", interval_s=60.0,
                                 buffer_events=4, timeout_s=0.2)
        for i in range(6):
            tracing.add_event("t.buf_spam", float(i), 1.0)
        assert not agent.ship()      # nothing listens on port 1
        dropped = obs.snapshot()[
            "paddle_tpu_fleet_agent_dropped_events_total"]["series"]
        assert dropped[("buffer",)] == 2
        # the surviving 4 moved into the frozen pending bundle; the
        # buffer now accumulates toward the NEXT bundle
        assert len(agent._buffer) == 0
        assert len(agent._pending[0]["trace"]) == 4

    def test_lost_ack_redelivery_commits_without_double_or_loss(self):
        """Merged-but-ack-lost: the retry redelivers the FROZEN bundle
        verbatim, the aggregator dedupes it, and the agent commits on
        the duplicate-ack — nothing double-merges and nothing grown
        between attempts is lost."""
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet
        obs.enable()
        agg = fleet.serve_aggregator()
        c = obs.registry().counter("t_ack_total", "")
        agent = fleet.FleetAgent(agg.endpoint, process="pl",
                                 role="replica", interval_s=60.0,
                                 timeout_s=5.0)
        c.inc(5)
        # attempt 1: the send "fails" after the aggregator merged it
        # (lost ack) — simulated by freezing the bundle via a dead
        # transport, then delivering that exact bundle out of band
        real_rpc = fleet._rpc
        fleet._rpc = lambda: (_ for _ in ()).throw(
            ConnectionError("chaos"))
        try:
            assert not agent.ship()
        finally:
            fleet._rpc = real_rpc
        fleet._ingest_bundle(agent._pending[0])
        c.inc(7)                     # grows between attempts
        assert agent.ship()          # redelivery -> duplicate-ack
        assert agent._seq == 1 and agent._pending is None
        assert _snap_series(agg.registry, "t_ack_total")[("pl",)] == 5
        assert agent.ship()          # next bundle carries the growth
        assert _snap_series(agg.registry, "t_ack_total")[("pl",)] == 12
        assert _snap_series(
            agg.registry,
            "paddle_tpu_fleet_duplicate_bundles_total")[("pl",)] == 1
        agg.close()

    def test_custom_registry_agent_self_accounts_in_it(self):
        """An agent shipping a custom registry keeps its own
        shipped/failures/dropped counters THERE — the plane observes
        itself in whichever store it ships."""
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import MetricsRegistry, fleet
        obs.enable()
        reg = MetricsRegistry()
        agent = fleet.FleetAgent("127.0.0.1:1", process="pc", role="r",
                                 interval_s=60.0, timeout_s=0.2,
                                 registry=reg)
        assert not agent.ship()
        assert _snap_series(
            reg, "paddle_tpu_fleet_agent_ship_failures_total")[()] == 1
        assert "paddle_tpu_fleet_agent_ship_failures_total" not in \
            obs.snapshot() or obs.snapshot()[
                "paddle_tpu_fleet_agent_ship_failures_total"][
                    "series"].get((), 0) == 0

    def test_background_thread_and_farewell(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet
        obs.enable()
        agg = fleet.serve_aggregator()
        c = obs.registry().counter("t_bg_total", "")
        agent = fleet.FleetAgent(agg.endpoint, process="pt",
                                 role="replica", interval_s=0.1)
        agent.start()
        c.inc(2)
        deadline = time.time() + 10.0
        while time.time() < deadline:
            s = agg.registry.snapshot().get("t_bg_total")
            if s and s["series"].get(("pt",)) == 2:
                break
            time.sleep(0.05)
        c.inc(4)                    # lands via the stop() farewell
        agent.stop()
        assert _snap_series(agg.registry, "t_bg_total")[("pt",)] == 6
        agg.close()


# ---------------------------------------------------------------------------
# capacity records + obs_top fleet panel
# ---------------------------------------------------------------------------
def _capacity_agg(tok_pa=500.0, tok_pb=250.0):
    """Aggregator with two replica processes' worth of engine counters
    over a ~10s reporting window. Rates measure growth past the FIRST
    bundle, so a heartbeat-only bundle establishes the zero baseline
    and a second bundle carries the work."""
    from paddle_tpu.observability import MetricsRegistry, fleet
    from paddle_tpu.observability import metrics as _m
    from paddle_tpu.observability.fleet import FleetAggregator
    _m.enable()
    agg = FleetAggregator(stale_after_s=60.0)
    for proc, tok in (("pa", tok_pa), ("pb", tok_pb)):
        agg.ingest(fleet.make_bundle(proc, "replica", 1))
        src = MetricsRegistry()
        src.counter("paddle_tpu_engine_events_total", "t",
                    ("event",)).labels(event="decode_tokens").inc(tok)
        src.counter("paddle_tpu_request_finished_total", "t",
                    ("reason",)).labels(reason="eos").inc(tok / 50)
        src.gauge("paddle_tpu_roofline_utilization", "t",
                  ("family", "bound")).labels(
            family="engine_ragged", bound="hbm").set(0.42)
        src.gauge("paddle_tpu_engine_queue_depth", "t",
                  ("queue",)).labels(queue="running").set(3)
        agg.ingest(fleet.make_bundle(
            proc, "replica", 2,
            metrics_delta=fleet.delta_snapshot(src.snapshot(), None)))
        agg._procs[proc]["first_seen"] -= 10.0   # give rates a window
    return agg


class TestCapacityRecords:
    def test_capacity_records(self):
        agg = _capacity_agg()
        recs = {r["process"]: r for r in agg.capacity_records()}
        pa = recs["pa"]
        assert pa["process_role"] == "replica"
        assert pa["tokens_total"] == 500.0
        assert pa["tok_per_s"] == pytest.approx(50.0, rel=0.2)
        assert pa["req_per_s"] == pytest.approx(1.0, rel=0.2)
        assert pa["utilization_hbm"] == 0.42
        assert recs["pb"]["tok_per_s"] == pytest.approx(25.0, rel=0.2)

    def test_first_bundle_history_excluded_from_rates(self):
        """A process whose first bundle carries a long pre-agent
        history must not have that history rated over the inter-bundle
        window (it would inflate req/s / tok/s by orders of magnitude
        — the exact number the elastic scaler sizes fleets from)."""
        from paddle_tpu.observability import MetricsRegistry, fleet
        from paddle_tpu.observability import metrics as _m
        from paddle_tpu.observability.fleet import FleetAggregator
        _m.enable()
        agg = FleetAggregator(stale_after_s=60.0)
        src = MetricsRegistry()
        tokc = src.counter("paddle_tpu_engine_events_total", "t",
                           ("event",)).labels(event="decode_tokens")
        tokc.inc(10000)             # pre-agent history
        base = src.snapshot()
        agg.ingest(fleet.make_bundle(
            "ph", "replica", 1,
            metrics_delta=fleet.delta_snapshot(base, None)))
        tokc.inc(100)               # actual in-window work
        agg.ingest(fleet.make_bundle(
            "ph", "replica", 2,
            metrics_delta=fleet.delta_snapshot(src.snapshot(), base)))
        agg._procs["ph"]["first_seen"] -= 10.0
        rec = agg.capacity_records()[0]
        assert rec["tokens_total"] == 10100.0    # totals keep history
        assert rec["tok_per_s"] == pytest.approx(10.0, rel=0.2)


class TestObsTopFleetPanel:
    def _obs_top(self):
        tools = os.path.join(REPO, "tools")
        sys.path.insert(0, tools)
        try:
            import obs_top
        finally:
            sys.path.remove(tools)
        return obs_top

    def test_renders_processes_and_staleness(self):
        obs_top = self._obs_top()
        agg = _capacity_agg()
        agg._procs["pb"]["last_seen"] -= 3600.0   # long gone
        agg.stale_after_s = 60.0
        doc = json.loads(agg.to_json())
        frame = obs_top.render_fleet(doc)
        assert "== fleet ==" in frame
        pa_line = [ln for ln in frame.splitlines() if "pa" in ln][0]
        pb_line = [ln for ln in frame.splitlines() if "pb" in ln][0]
        assert "up" in pa_line and "inflight=  3" in pa_line
        assert "STALE" in pb_line
        assert "bundles=4" in frame
        # the full dashboard embeds the same panel
        assert "== fleet ==" in obs_top.render(doc)
        # tok/s rate appears between frames
        prev = doc
        agg2 = _capacity_agg(tok_pa=600.0)
        frame2 = obs_top.render_fleet(json.loads(agg2.to_json()),
                                      prev, dt=1.0)
        assert "tok/s" in frame2

    def test_no_fleet_series_renders_nothing(self):
        obs_top = self._obs_top()
        assert obs_top.render_fleet({}) == ""


# ---------------------------------------------------------------------------
# disabled-mode overhead guard (two same-call-site windows — the
# interpreter retains ~2KB per call path regardless of iterations)
# ---------------------------------------------------------------------------
class TestDisabledOverhead:
    def test_agent_and_rpc_context_paths_allocate_nothing(self):
        import tracemalloc
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet, tracing
        from paddle_tpu.distributed import rpc
        assert not obs.enabled()
        c = obs.registry().counter("t_ov_fleet_total", "")
        # an agent merely existing must not change hot-path cost
        fleet.FleetAgent("127.0.0.1:1", process="pov", role="r",
                         interval_s=3600.0)
        rpc._obs()                   # warm the lazy handles

        def window(n):
            tracemalloc.start()
            base = tracemalloc.get_traced_memory()[0]
            for _ in range(n):
                c.inc()
                with tracing.span("t.ov_fleet"):
                    pass
                with tracing.trace_context("00" * 8, "00" * 4):
                    pass
                # the rpc client/server guard branches
                if rpc._obs()["m"]._ENABLED or rpc._obs()["t"].enabled():
                    pytest.fail("observability unexpectedly enabled")
            grown = tracemalloc.get_traced_memory()[0] - base
            tracemalloc.stop()
            return grown

        g1 = window(4000)
        g2 = window(4000)
        assert abs(g2 - g1) < 2048, (g1, g2)
        assert tracing.events() == []


# ---------------------------------------------------------------------------
# the real spawn boundary: N workers ship to an aggregator process,
# one killed -9 mid-run
# ---------------------------------------------------------------------------
def _remote_mark(name):
    """Executed in the AGGREGATOR process via rpc — its rpc.server
    span lands in the aggregator's ring, completing the cross-process
    tree whose client half ships with the worker's bundle."""
    return name


def _fleet_worker(endpoint, name, kill_self, q):
    """Spawned worker: records metrics + a traced cross-process RPC,
    ships two sequence-numbered deltas, reports what it shipped, then
    either dies hard (kill_self) or stops cleanly with a farewell."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet, tracing
        from paddle_tpu.distributed import rpc

        obs.enable()
        fleet.set_identity(process=name, role="replica")
        c = obs.registry().counter("paddle_tpu_test_fleet_work_total",
                                   "test work items")
        agent = fleet.FleetAgent(endpoint, interval_s=60.0,
                                 timeout_s=30.0)
        with tracing.span("t.fleet_work", worker=name):
            assert rpc.call_endpoint(endpoint, _remote_mark,
                                     args=(name,), timeout=30.0) == name
        c.inc(5)
        ok1 = agent.ship()
        c.inc(7)
        ok2 = agent.ship()
        q.put((name, 12 if (ok1 and ok2) else None, agent._seq))
        if kill_self:
            time.sleep(1.0)          # let the queue feeder flush
            os.kill(os.getpid(), signal.SIGKILL)
        c.inc(3)
        agent.stop()                 # farewell carries the last 3
    except BaseException as e:       # report instead of hanging parent
        q.put((name, f"ERROR: {e!r}", -1))
        raise


class TestMultiProcessFleet:
    def test_workers_ship_kill9_marks_stale_no_double_count(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import fleet, tracing
        obs.enable()
        agg = fleet.serve_aggregator(stale_after_s=2.0)
        ctx = multiprocessing.get_context("spawn")
        q = ctx.Queue()
        w1 = ctx.Process(target=_fleet_worker,
                         args=(agg.endpoint, "w1", True, q))
        w2 = ctx.Process(target=_fleet_worker,
                         args=(agg.endpoint, "w2", False, q))
        w1.start()
        w2.start()
        reports = {}
        for _ in range(2):
            name, shipped, seq = q.get(timeout=180)
            reports[name] = (shipped, seq)
        w1.join(60)
        w2.join(60)
        assert w1.exitcode == -signal.SIGKILL
        assert w2.exitcode == 0
        assert reports["w1"][0] == 12 and reports["w2"][0] == 12

        snap = agg.registry.snapshot()
        work = snap["paddle_tpu_test_fleet_work_total"]["series"]
        # every acknowledged delta retained, none double-counted (the
        # sequence numbers the workers reported match the aggregator's
        # accepted seq per process)
        assert work[("w1",)] == 12.0
        assert work[("w2",)] == 12.0 + 3.0   # + the farewell ship
        assert agg.processes()["w1"]["last_seq"] == reports["w1"][1]
        # every process label present in the merged exposition
        expo = agg.to_prometheus()
        assert 'process="w1"' in expo and 'process="w2"' in expo
        # the killed worker goes stale within the configured window
        deadline = time.time() + 15.0
        while time.time() < deadline and agg.health()["w1"]["up"]:
            time.sleep(0.2)
        assert not agg.health()["w1"]["up"]

        # one connected cross-process trace per worker: the worker's
        # rpc.client span (its pid, shipped in the bundle) parents the
        # aggregator-side rpc.server span (this pid)
        evs = tracing.events()
        my_pid = os.getpid()
        for wname in ("w1", "w2"):
            roots = [e for e in evs if e["name"] == "t.fleet_work"
                     and e.get("args", {}).get("worker") == wname]
            assert len(roots) == 1, wname
            root = roots[0]
            assert root["pid"] != my_pid
            clients = [e for e in evs if e["name"] == "rpc.client"
                       and e.get("parent_id") == root["span_id"]]
            assert len(clients) == 1, wname
            client = clients[0]
            assert client["trace_id"] == root["trace_id"]
            servers = [e for e in evs if e["name"] == "rpc.server"
                       and e.get("parent_id") == client["span_id"]]
            assert servers and all(
                s["trace_id"] == root["trace_id"]
                and s["pid"] == my_pid for s in servers), wname
        agg.close()
