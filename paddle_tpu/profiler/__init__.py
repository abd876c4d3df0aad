"""Profiler (ref: python/paddle/profiler/profiler.py:346 + C++ host/device
tracers §5.1).

Host spans: a RecordEvent IS an `observability.tracing.span` — ONE event
stream, so `export_chrome_tracing` here and the observability exporters
produce consistent files whichever API recorded the span. Device
timeline: `Profiler` (unless `timer_only`) also starts `jax.profiler`
(XLA/PJRT trace), and every span open while it records is entered as a
profiler annotation too: the `.xplane.pb` under `PADDLE_TPU_TRACE_DIR`
then holds the program's spans, TPU kernels, transfers and host
callbacks on one clock. Read it with
`python3 benchmarks/tools/scope_table.py <file.xplane.pb>` (device time
by `jax.named_scope` component, the spans' self times) or in
tensorboard/Perfetto."""
from __future__ import annotations

import json
import os
import threading
import time
from enum import Enum
from typing import Callable, List, Optional

import jax

from ..observability import tracing as _tracing


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3

# --- per-op dispatch spans (ref: eager_gen.py:251 "Dygraph Record
# Event" slot — the reference opens a platform::RecordEvent in every
# generated ad_func; here ops.registry._dispatch_profiled reports into
# this aggregator; the profiler swaps the live dispatch pointer so the
# non-recording path pays nothing). chrome-trace events are NOT emitted
# per op (that would distort the timeline the XLA trace covers).
_op_stats: dict = {}
_op_stats_lock = threading.Lock()


def _record_op(name: str, t0_ns: int, cached: bool) -> None:
    dur = (time.perf_counter_ns() - t0_ns) / 1e6
    with _op_stats_lock:
        st = _op_stats.get(name)
        if st is None:
            st = _op_stats[name] = [0, 0.0, 0.0, 0]  # calls,total,max,hits
        st[0] += 1
        st[1] += dur
        if dur > st[2]:
            st[2] = dur
        if cached:
            st[3] += 1


class RecordEvent:
    """(ref: paddle.profiler.RecordEvent / C++ platform/profiler/
    event_tracing.h:43)

    Idempotent: a second end() (or __exit__ after an explicit end()) is
    a no-op — the span is consumed by the first end. It opens an
    `observability.tracing.span`: the event lands in the shared trace
    ring when tracing is enabled at begin() (by a running Profiler or by
    observability.enable()) and in the profiler's trace while a
    `jax.profiler` session records."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._span = None

    def begin(self):
        self._span = _tracing.span(self.name)
        self._span.__enter__()

    def end(self):
        sp, self._span = self._span, None   # consume: double end no-ops
        if sp is not None:
            sp.end()

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed=0, ready=1, record=4, repeat=0, skip_first=0):
    total = closed + ready + record

    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        if repeat and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: str = None):
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        fname = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name, f"{fname}.pb.trace.json")
        with open(path, "w") as f:
            json.dump({"traceEvents": prof.events()}, f)
        return path

    return handler


class Profiler:
    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self._jax_trace_dir = None

    def start(self):
        # one event stream: Profiler sessions record into the shared
        # observability ring. start() clears it (a profiling session is
        # a fresh window); tracing stays enabled afterwards only if
        # observability had it on before this session.
        self._trace_was_enabled = _tracing.enabled()
        _tracing.clear()
        _tracing.enable()
        from ..ops import registry as _registry
        _registry._set_op_profiling(True)
        _op_stats.clear()
        if not self.timer_only:
            self._jax_trace_dir = os.environ.get(
                "PADDLE_TPU_TRACE_DIR", "/tmp/paddle_tpu_trace")
            try:
                jax.profiler.start_trace(self._jax_trace_dir)
            except Exception:
                self._jax_trace_dir = None
        return self

    def stop(self):
        if not getattr(self, "_trace_was_enabled", False):
            _tracing.disable()
        from ..ops import registry as _registry
        _registry._set_op_profiling(False)
        if self._jax_trace_dir is not None:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_trace_dir = None
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        self.step_num += 1

    def events(self):
        return _tracing.events()

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        lines = []
        with _op_stats_lock:
            op_rows = sorted(_op_stats.items(), key=lambda kv: -kv[1][1])
        if op_detail and op_rows:
            # per-op dispatch table (ref: profiler_statistic.py
            # "Operator Summary" — calls / total / avg / max host time
            # + executable-cache hit ratio, this backend's analog of
            # the reference's kernel-launch breakdown)
            lines.append("-------------------  Operator Summary  "
                         "-------------------")
            lines.append(f"{'op':<36} {'calls':>7} {'total_ms':>10} "
                         f"{'avg_ms':>8} {'max_ms':>8} {'cache%':>7}")
            for name, (n, tot, mx, hits) in op_rows:
                lines.append(
                    f"{name:<36} {n:>7} {tot:>10.3f} {tot / n:>8.3f} "
                    f"{mx:>8.3f} {100.0 * hits / n:>6.1f}%")
        evs = self.events()
        agg = {}
        for e in evs:
            a = agg.setdefault(e["name"], [0.0, 0])
            a[0] += e["dur"] / 1000.0
            a[1] += 1
        if agg:
            lines.append("-------------------  UserDefined Summary  "
                         "-----------------")
            lines.append(f"{'name':<50} {'calls':>8} {'total_ms':>12}")
            for name, (tot, n) in sorted(agg.items(),
                                         key=lambda kv: -kv[1][0]):
                lines.append(f"{name:<50} {n:>8} {tot:>12.3f}")
        return "\n".join(lines)

    def op_stats(self):
        """Raw per-op rows: {name: (calls, total_ms, max_ms, cache_hits)}."""
        with _op_stats_lock:
            return {k: tuple(v) for k, v in _op_stats.items()}

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)
