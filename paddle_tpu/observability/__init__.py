"""Unified observability: metrics registry + structured tracing +
request-scoped lifecycle instrumentation (see README "Observability"
and "Request tracing & SLOs").

The subsystem is the connective tissue the serving/perf work reads its
numbers from. Built-in instrumentation (recorded only while enabled):

* `inference.LLMEngine` — step latency, prefill / decode-chunk timing
  histograms, waiting/running queue-depth and page-pool gauges, every
  `engine.stats` counter mirrored as
  `paddle_tpu_engine_events_total{event=...}`, per-request
  TTFT / TPOT / queue-wait / e2e latency histograms
  (`paddle_tpu_request_*_seconds`), compile counters + wall-time by
  executable family, and HBM gauges sampled at step boundaries. Every
  request's admission → queue wait → prefill → decode chunks →
  preemption/resume → finish forms ONE connected trace (shared
  trace_id, parented to a per-request root span).
* `io.DataLoader` — batch wait latency (consumer side), worker batch
  produce latency + batch counts AND worker-side trace events
  (recorded IN spawned workers and merged into the parent when each
  worker finishes), worker restarts, SharedMemory bytes.
* `distributed.checkpoint` — save/restore duration, shard bytes, torn
  checkpoints skipped/quarantined by `resume_latest`.
* `optimizer` fused step — executable-cache hits / compiles (misses) /
  eager fallbacks, plus compile wall time.
* `profiler.RecordEvent` — opens the same `span()`, so both exporters
  see one event stream.
* `jit.TrainStep` — `train_step` / `train_step.feed` /
  `train_step.dispatch` spans; every `CompileTimed` first call —
  `compile.lower` (and inside it `compile.trace`) / `compile.backend` /
  `compile.first_run` spans and `perf.compile_record(family)` (`lower_s`
  and its part `trace_s`, `backend_s`, `first_run_s`, `trace_by_scope`:
  the trace's seconds by layer path and kernel), written metrics on or
  off.
* set-up — `setup.build.model` / `.params` / `.optimizer` /
  `.train_step` spans where layers, parameters, accumulators and the
  `TrainStep` are built; their seconds and clock positions, the
  package's `import` and every first call's parts in
  `perf.setup_record()`, and every program JAX traces, lowers, compiles
  or loads in `perf.program_log()`, both written metrics on or off.

Every span is also a profiler annotation (`tracing.py`) while a profiler
session records (tracing enabled or not): the program's spans then lie
in the `.xplane.pb` on the device's clock, beside device operations
that carry the layers' `jax.named_scope` paths
(`python3 benchmarks/tools/scope_table.py <file.xplane.pb>`).

Sub-surfaces: `observability.slo` (declarative latency objectives
evaluated from the registry), `observability.flight` (anomaly flight
recorder — atomic metrics+trace bundles on slow steps, deadline
misses, preemption storms, fault-point fires, SLO breaches, training
numerics divergence), `observability.numerics` (the training-health
plane: in-trace grad/param stats with one async pull per sampled
step, the NaN/Inf sentinel with per-parameter attribution, AMP
loss-scale forensics — see README "Training numerics & model
health"), and `observability.fleet` (the cross-process plane:
per-process obs agents ship sequence-numbered metric deltas + trace
events + heartbeats over the HMAC RPC layer to an aggregator that
merges them under a `process` label and publishes fleet health — see
README "Fleet observability").

Quick start::

    from paddle_tpu import observability as obs
    obs.enable()
    ...            # run the workload
    print(obs.to_prometheus())
    obs.export_chrome_trace("/tmp/trace.json")

`enable()`/`disable()` flip metrics AND tracing together; the
submodules expose the flags separately for finer control
(`obs.metrics.enable()`, `obs.tracing.enable()`). Everything is
process-global; `snapshot()` / `merge()` carry metrics across spawn
boundaries (the DataLoader does this automatically for its workers,
shipping trace events alongside)."""
from __future__ import annotations

from . import comms, fleet, flight, metrics, numerics, perf, slo, tracing  # noqa: F401
from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, registry,
    DEFAULT_BUCKETS, MergeSkewError,
)
from .tracing import (  # noqa: F401
    span, current_trace, trace_context, export_chrome_trace,
    export_jsonl,
)
from .slo import SLO  # noqa: F401

__all__ = [
    "enable", "disable", "enabled", "registry", "snapshot", "merge",
    "reset", "to_prometheus", "to_json", "span", "current_trace",
    "trace_context", "trace_events", "trace_clear",
    "export_chrome_trace", "export_jsonl", "summary",
    "metrics", "tracing", "slo", "flight", "perf", "fleet", "comms",
    "numerics", "SLO",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "DEFAULT_BUCKETS", "MergeSkewError",
]


def enable() -> None:
    """Enable metric recording and tracing, process-wide."""
    metrics.enable()
    tracing.enable()


def disable() -> None:
    metrics.disable()
    tracing.disable()


def enabled() -> bool:
    return metrics.enabled()


def snapshot() -> dict:
    return registry().snapshot()


def merge(snap: dict, on_skew: str = "raise") -> list:
    """Aggregate a snapshot() into the process-global registry; see
    MetricsRegistry.merge for the schema-skew contract (raise a
    MergeSkewError by default, or route skewed series to quarantined
    names with on_skew="quarantine")."""
    return registry().merge(snap, on_skew=on_skew)


def reset() -> None:
    """Full observable-state reset: zero every metric series AND drop
    every buffered trace event — the two stores move together so a
    fresh measurement window never mixes old spans with new counters
    (pinned by test_reset_clears_metrics_and_trace_ring). Use
    `trace_clear()` for the narrow ring-only clear. The goodput
    accounting's collective seconds move with it (comms.reset_window;
    the per-process call-seq counters survive, see there), and so do
    the numerics plane's pending bundle, sentinel windows and
    divergence latch (numerics.reset_window — the enabled flag and
    config survive)."""
    registry().reset()
    tracing.clear()
    comms.reset_window()
    numerics.reset_window()


def to_prometheus() -> str:
    return registry().to_prometheus()


def to_json() -> str:
    return registry().to_json()


def trace_events() -> list:
    return tracing.events()


def trace_clear() -> None:
    """Drop buffered trace events only (metrics keep counting)."""
    tracing.clear()


def summary() -> dict:
    """Compact summary for machine consumers: non-zero counters/gauges
    as flat `name{k=v}` keys and per-histogram
    {count, sum, mean, min, max, p50, p95} — the
    percentile estimates come from the bucket vectors
    (metrics.quantile_from_buckets), which stay out of the summary
    themselves; use to_prometheus()/to_json() for those."""
    out = {"counters": {}, "gauges": {}, "histograms": {}}
    for name, rec in snapshot().items():
        for key, val in sorted(rec["series"].items()):
            lbl = name if not key else name + "{" + ",".join(
                f"{k}={v}" for k, v in zip(rec["labelnames"], key)) + "}"
            if rec["kind"] == "histogram":
                if val["count"]:
                    entry = {
                        "count": val["count"],
                        "sum": round(val["sum"], 6),
                        "mean": round(val["sum"] / val["count"], 6),
                        "min": round(val["min"], 6),
                        "max": round(val["max"], 6),
                    }
                    for pname, q in (("p50", 0.5), ("p95", 0.95)):
                        est = metrics.quantile_from_buckets(
                            rec["buckets"], val["buckets"], q,
                            lo=val["min"], hi=val["max"])
                        if est is not None:
                            entry[pname] = round(est, 6)
                    out["histograms"][lbl] = entry
            elif val:
                out["counters" if rec["kind"] == "counter"
                    else "gauges"][lbl] = val
    return {k: v for k, v in out.items() if v}
