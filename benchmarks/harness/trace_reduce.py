"""From a profiler trace (`.xplane.pb`, read with `jax.profiler.ProfileData`)
to the numbers the per-layer readers ask for.

A TPU trace has one plane per chip (`/device:TPU:<n>`) whose `XLA Ops`
line holds every device operation (a `while` holds its body's
operations nested inside it) and whose `XLA Modules` line holds one
event for every run of a compiled program. The host's plane holds the
harness's `TraceAnnotation` spans (`harness.*`) on the same clock.

* window: from the first `harness.*` span's start to the last one's
  end — the part of the trace in which the harness was driving the
  system (the profiler's own start and stop lie outside it);
* busy: per chip, the union of the device operations' intervals cut to
  the window, averaged over the chips; idle share = 1 - busy / window;
* operation time by name: the summed *self* time (an operation's time
  less that of operations nested in it), so that a `while` and its body
  are not counted twice;
* kernels: the device operations whose HLO text is a `custom-call(` to
  `tpu_custom_call`. XLA names them after the jax operation they came
  from (`ragged.24`, `jvp__.3`, `transpose_jvp___.18`, `checkpoint.2`),
  not after the kernel, until the program gives its kernels stable
  names (the `tracing` issue);
* gaps: the idle intervals of chip 0, each named by the innermost
  `harness.*` span that covers its middle.
"""
from __future__ import annotations

import bisect
import functools
import re
import statistics
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "harness."
CUSTOM_CALL = "custom-call("     # in an operation's HLO text


def is_kernel(op_name: str) -> bool:
    """Whether a device operation is a Pallas kernel: a custom call whose
    target is `tpu_custom_call`. XLA's own bookkeeping custom calls (named
    `custom-call.N`, thousands in a step, no duration) are not."""
    if CUSTOM_CALL not in op_name:
        return False
    return "tpu_custom_call" in op_name or \
        not short_name(op_name).startswith("custom-call")


def union_seconds(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """The complement of the union of intervals inside [lo, hi]."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events):
    """[(name, start, end)] -> [(name, self seconds)]: each event's
    duration less its directly nested events' (same line, flame order)."""
    out, stack = [], []        # stack of [name, end, self]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= (min(e, stack[-1][1]) - s)
        stack.append([name, e, e - s])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2]))
    return out


class Trace:
    """The reduced trace. Times are seconds on the trace's own clock."""

    def __init__(self, device_ops, device_modules, spans):
        # device_ops / device_modules: {plane: [(name, start, end)]}
        self.device_ops = device_ops
        self.device_modules = device_modules
        self.spans = sorted(spans, key=lambda ev: ev[1])
        self._span_starts = [s for _n, s, _e in self.spans]
        self._self_times = None
        if self.spans:
            self.lo = min(s for _n, s, _e in self.spans)
            self.hi = max(e for _n, _s, e in self.spans)
        else:
            every = [ev for evs in device_ops.values() for ev in evs]
            self.lo = min((s for _n, s, _e in every), default=0.0)
            self.hi = max((e for _n, _s, e in every), default=0.0)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        ops, modules, spans = {}, {}, []
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name in (OPS_LINE, MODULES_LINE):
                        evs = [(ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9)
                               for ev in line.events]
                        (ops if line.name == OPS_LINE
                         else modules)[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(ev.name, ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9)
                              for ev in line.events
                              if ev.name.startswith(SPAN_PREFIX)]
        return cls(ops, modules, spans)

    # -- the window -------------------------------------------------------
    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def _clipped(self, events):
        return [(max(s, self.lo), min(e, self.hi)) for _n, s, e in events
                if e > self.lo and s < self.hi]

    @functools.cached_property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        if not self.device_ops:
            return 0.0
        return sum(union_seconds(self._clipped(evs))
                   for evs in self.device_ops.values()) / len(
                       self.device_ops)

    def idle_share_pct(self):
        if not self.device_ops or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    # -- operations ---------------------------------------------------------
    def _inside(self, events):
        return [ev for ev in events if ev[1] >= self.lo and ev[2] <= self.hi]

    def op_self_times(self):
        """[(name, self seconds)] of chip 0's operations in the window."""
        if not self.device_ops:
            return []
        if self._self_times is None:
            first = sorted(self.device_ops)[0]
            self._self_times = self_times(
                self._inside(self.device_ops[first]))
        return self._self_times

    def op_seconds(self, pattern: str):
        """(seconds, count) of chip 0's operations whose name matches."""
        rx = re.compile(pattern)
        hits = [t for n, t in self.op_self_times() if rx.search(n)]
        return sum(hits), len(hits)

    def module_runs(self, pattern: str):
        """Seconds of each of chip 0's program runs whose name matches."""
        if not self.device_modules:
            return []
        rx = re.compile(pattern)
        first = sorted(self.device_modules)[0]
        return [e - s for n, s, e in self._inside(self.device_modules[first])
                if rx.search(n)]

    def module_seconds(self, pattern: str):
        """(seconds, runs) of chip 0's program runs whose name matches."""
        hits = self.module_runs(pattern)
        return sum(hits), len(hits)

    def module_run_s(self, pattern: str):
        """The median run of a program, or None. The trace begins and
        ends in the middle of a run, and the profiler records the part it
        saw as a run of its own: on the chip six runs of a 719 ms step,
        the last cut short, gave a mean of 602 ms (PERF.md section 6). A
        cut run does not move the median."""
        hits = self.module_runs(pattern)
        return statistics.median(hits) if hits else None

    def module_period_s(self, pattern: str):
        """Median time from one run of a program to the next (start to
        start) in the window: the device's own step cadence. The median,
        because a first run the trace's edge cut starts late: on the chip
        23 intervals of a 128.0 ms step, the first one cut, gave a mean
        of 125.7 ms (PERF.md section 6)."""
        if not self.device_modules:
            return None
        rx = re.compile(pattern)
        first = sorted(self.device_modules)[0]
        starts = sorted(s for n, s, e in
                        self._inside(self.device_modules[first])
                        if rx.search(n))
        if len(starts) < 2:
            return None
        return statistics.median(b - a for a, b in zip(starts, starts[1:]))

    def kernel_calls(self):
        """{stem: [calls, seconds]} of chip 0's custom calls (the Pallas
        kernels) in the window, by name without its number."""
        by = defaultdict(lambda: [0, 0.0])
        for name, t in self.op_self_times():
            if is_kernel(name):
                stem = re.sub(r"\.\d+$", "", short_name(name))
                by[stem][0] += 1
                by[stem][1] += t
        return dict(by)

    # -- the breakdown --------------------------------------------------------
    def top_ops(self, k=10):
        by = defaultdict(float)
        for name, t in self.op_self_times():
            by[name] += t
        return [[short_name(n), t] for n, t in
                sorted(by.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k=10):
        if not self.device_ops:
            return []
        first = sorted(self.device_ops)[0]
        by = defaultdict(float)
        for s, e in gaps(self._clipped(self.device_ops[first]),
                         self.lo, self.hi):
            by[self._span_at((s + e) / 2)] += e - s
        return [[n, t] for n, t in
                sorted(by.items(), key=lambda kv: -kv[1])[:k]]

    def _span_at(self, t: float) -> str:
        """The innermost span over `t`: the latest-started one that has
        not ended by then."""
        for i in range(bisect.bisect_right(self._span_starts, t) - 1,
                       -1, -1):
            if self.spans[i][2] >= t:
                return self.spans[i][0]
        return "unannotated"

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def short_name(name: str, limit: int = 64) -> str:
    """An operation's name without its shapes: what stands before the
    first ' = ' or '(' of the HLO text the profiler may give."""
    name = name.split(" = ")[0].split("(")[0].strip().lstrip("%")
    return name[:limit]
