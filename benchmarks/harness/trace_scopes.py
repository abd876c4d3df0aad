"""From a profiler trace to device time by the program's own names, and
to the program's own spans.

`paddle_tpu` runs every `Layer.forward` of a traced program under
`jax.named_scope(<the name its parent holds it by>)` and scopes what is
no Layer by hand (`lm_head`, `optimizer`, `flash_fwd`,
`flash_bwd_transpose`, `ragged_attention`, `decode_attention`), so every
operation's `op_name` is a path such as

    jit(step)/transpose(jvp(gptforcausallm))/gpt/layers/3/attn/qkv_proj/dot_general

The TPU's trace keeps it: each event of a device plane's `XLA Ops` line
points at an entry of the plane's `event_metadata`, and that entry's
stats hold `tf_op` (the `op_name`), beside `hlo_category`, `flops` and
`bytes_accessed`. `jax.profiler.ProfileData` hands out an event's own
stats only, so `XSpace` below decodes the `.xplane.pb` itself (protobuf
wire format, the fields of tsl's `xplane.proto` that are needed, nothing
imported). Events are joined to their metadata by id, not by name: two
programs in one trace can hold an instruction of the same text under
different scopes.

* component: the path with JAX's wrappers (`jit(..)`, `jvp(..)`,
  `transpose(..)`), its markers (`checkpoint`, `rematted_computation`)
  and the primitive at its end taken off. `jax.checkpoint` starts the
  path again inside the block (`…/gpt/jvp(gptforcausallm)/gpt/checkpoint/
  layers/0/attn`): the component starts at the root's last occurrence.
* phase, from the same operation: `recompute` (under
  `rematted_computation`: what `jax.checkpoint` runs again), `xla_remat`
  (XLA's own rematerialisation: the instruction's name holds `.remat`,
  and the program still holds another instance of it; a lone clone is
  the instruction moved, not run again),
  `backward` (a `transpose(` wrapper), `forward` (a `jvp(` wrapper alone),
  else `plain` (outside what is differentiated: the optimizer's update).
* a fusion takes the scope its metadata gives, which is its root's:
  operations XLA fused into it from a neighbouring scope are counted
  with the root.
* self times come from `trace_reduce.self_times` and the window from
  `trace_reduce.Trace`, so the seconds are the ones the other readers
  see.
* a step is one run of the step's program (`XLA Modules` line); only
  whole runs count: the trace's edges cut the first and the last.
* the program's spans (`train_step*`, `compile.*`, `engine.*`: every
  `observability.tracing.span` is a profiler annotation while a session
  records) are read from the host planes; a span's self time is its
  duration less what its children cover.
"""
from __future__ import annotations

import bisect
import re
import statistics
import struct
from collections import defaultdict

from harness.trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                                  Trace, self_times, short_name)

SPAN_PREFIXES = ("train_step", "compile.", "engine.")
UNSCOPED = "(unscoped)"
PHASES = ("forward", "backward", "recompute", "xla_remat", "plain")
_MARKERS = {"checkpoint", "rematted_computation"}
_WRAPPED = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$", re.S)


# -- the wire format --------------------------------------------------------
def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of every field of one message:
    an int for a varint or a fixed-width field, a memoryview for a
    length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        no, wt = key >> 3, key & 7
        if wt == 0:
            value, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wt in (1, 5):
            size = 8 if wt == 1 else 4
            value = int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield no, wt, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf):
    """XStat -> (stat metadata id, value); a `ref_value` comes back as
    ("ref", id) for the plane to resolve."""
    key = value = None
    for no, wt, v in fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = _signed(v)
        elif no == 5:
            value = _text(v)
        elif no == 6:
            value = bytes(v)
        elif no == 7:
            value = ("ref", v)
    return key, value


class XPlane:
    """One plane: `lines` as [(name, [(metadata id, start s, end s)])],
    `event_names` {metadata id: name} and `event_stats`
    {metadata id: {stat name: value}}."""

    def __init__(self, buf):
        self.name = ""
        lines, event_meta, stat_names = [], {}, {}
        for no, _wt, v in fields(buf):
            if no == 2:
                self.name = _text(v)
            elif no == 3:
                lines.append(v)
            elif no == 4:
                event_meta.update([self._map_entry(v)])
            elif no == 5:
                key, meta = self._map_entry(v)
                stat_names[key] = next(
                    (_text(x) for n, _w, x in fields(meta) if n == 2), "")
        self.event_names, self.event_stats = {}, {}
        for key, meta in event_meta.items():
            stats = {}
            for no, _wt, v in fields(meta):
                if no == 2:
                    self.event_names[key] = _text(v)
                elif no == 5:
                    sid, value = _stat(v)
                    if isinstance(value, tuple):
                        value = stat_names.get(value[1], "")
                    stats[stat_names.get(sid, sid)] = value
            self.event_names.setdefault(key, "")
            self.event_stats[key] = stats
        self.lines = [self._line(v) for v in lines]

    @staticmethod
    def _map_entry(buf):
        key = value = None
        for no, _wt, v in fields(buf):
            if no == 1:
                key = v
            elif no == 2:
                value = v
        return key, value

    @staticmethod
    def _line(buf):
        name, t0_ns, events = "", 0, []
        for no, _wt, v in fields(buf):
            if no == 2:
                name = _text(v)
            elif no == 3:
                t0_ns = _signed(v)
            elif no == 4:
                events.append(v)
        out = []
        for ev in events:
            mid = off_ps = dur_ps = 0
            for no, _wt, v in fields(ev):
                if no == 1:
                    mid = v
                elif no == 2:
                    off_ps = _signed(v)
                elif no == 3:
                    dur_ps = _signed(v)
            start = (t0_ns + off_ps * 1e-3) * 1e-9
            out.append((mid, start, start + dur_ps * 1e-12))
        return name, out

    def line(self, name: str):
        for n, events in self.lines:
            if n == name:
                return events
        return []


class XSpace:
    """The planes of an `.xplane.pb`."""

    def __init__(self, planes):
        self.planes = planes

    @classmethod
    def from_file(cls, path: str) -> "XSpace":
        with open(path, "rb") as f:
            buf = memoryview(f.read())
        return cls([XPlane(v) for no, _wt, v in fields(buf) if no == 1])

    def device_planes(self):
        return sorted((p for p in self.planes
                       if DEVICE_PLANE.match(p.name)),
                      key=lambda p: p.name)

    def host_planes(self):
        return [p for p in self.planes if p.name.startswith("/host:")]


# -- an operation's scope ---------------------------------------------------
def op_name_of(tf_op: str) -> str:
    """The `op_name` in a metadata entry's `tf_op` stat, which reads
    `<op_name>:<type>` and, for an instruction XLA merged from several,
    `<op_name>;<op_name>:<type>`: the first."""
    return tf_op.rpartition(":")[0].split(";")[0] if ":" in tf_op \
        else tf_op.split(";")[0]


def split_path(op_name: str):
    """The path's elements: split at the slashes outside parentheses (a
    wrapped element may hold one: `jvp(layers/0)`)."""
    out, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")" and depth > 0
        cur.append(ch)
    out.append("".join(cur))
    return out


def unwrap(element: str):
    """`transpose(jvp(gpt))` -> ("gpt", ["transpose", "jvp"])."""
    wrappers = []
    while True:
        m = _WRAPPED.match(element)
        if not m:
            return element, wrappers
        wrappers.append(m.group(1))
        element = m.group(2)


def extra_clones(instructions):
    """Of a program's instruction names, XLA's rematerialised clones
    that are run in addition to another instance: `fusion.7.remat` and
    `fusion.7.remat2` beside `fusion.7`; of `fusion.9.remat` and
    `fusion.9.remat2` without a `fusion.9`, the second."""
    names = set(instructions)
    by_base = defaultdict(list)
    for name in names:
        base, sep, _n = name.partition(".remat")
        if sep:
            by_base[base].append(name)
    extra = set()
    for base, clones in by_base.items():
        extra.update(sorted(clones)[0 if base in names else 1:])
    return extra


def scope_of(op_name: str, instruction: str = "", rerun: bool = None):
    """(program, component, phase) of one operation, from its `op_name`
    and its instruction's name. The component is "" where the operation
    lies in no scope of the program. `rerun` says whether a `.remat`
    clone runs beside another instance (`extra_clones`); not given, every
    clone counts as run again."""
    program, names, wrappers = "", [], []
    elements = split_path(op_name or "")
    for element in elements[:-1]:       # the last one is the primitive
        name, ws = unwrap(element)
        wrappers += ws
        if "jit" in ws or "pjit" in ws:
            program = program or name   # a function's name, not a scope
        else:
            names.append(name)
    if names:
        # jax.checkpoint starts the path again inside the block
        last = len(names) - 1 - names[::-1].index(names[0])
        names = names[last:]
    recomputed = "rematted_computation" in names
    component = "/".join(n for n in names if n and n not in _MARKERS)
    if ".remat" in instruction and rerun is not False:
        phase = "xla_remat"
    elif recomputed:
        phase = "recompute"
    elif "transpose" in wrappers:
        phase = "backward"
    elif "jvp" in wrappers:
        phase = "forward"
    else:
        phase = "plain"     # outside what is differentiated: the update
    return program, component, phase


# -- the reduction ------------------------------------------------------------
class ScopedTrace:
    """Chip 0's operations by scope and the host's program spans, over
    the window `trace_reduce.Trace` gives the same file."""

    def __init__(self, space: XSpace, trace: Trace):
        # the harness's spans bound the window; a trace without them
        # (an operator's own session) counts whole
        self.lo, self.hi = (trace.lo, trace.hi) if trace.spans \
            else (float("-inf"), float("inf"))
        planes = space.device_planes()
        self.plane = planes[0] if planes else None
        self.host = space.host_planes()
        self._extra = self._ops = None
        self._scopes = {}

    @classmethod
    def from_file(cls, path: str, trace: Trace = None) -> "ScopedTrace":
        return cls(XSpace.from_file(path), trace or Trace.from_file(path))

    def _inside(self, events):
        return [ev for ev in events
                if ev[1] >= self.lo and ev[2] <= self.hi]

    # -- device ---------------------------------------------------------------
    def scope(self, mid: int):
        """(program, component, phase) of an `XLA Ops` metadata id."""
        if mid in self._scopes:
            return self._scopes[mid]
        if self._extra is None:
            # instruction names repeat across programs: by program
            programs = defaultdict(list)
            for m, stats in self.plane.event_stats.items():
                programs[stats.get("program_id")].append(
                    short_name(self.plane.event_names[m]))
            self._extra = {(program, name)
                           for program, names in programs.items()
                           for name in extra_clones(names)}
        name = short_name(self.plane.event_names.get(mid, ""))
        stats = self.plane.event_stats.get(mid, {})
        self._scopes[mid] = scope_of(
            op_name_of(stats.get("tf_op", "")), name,
            (stats.get("program_id"), name) in self._extra)
        return self._scopes[mid]

    def ops(self):
        """[(metadata id, start, self seconds)] of chip 0's operations in
        the window: `trace_reduce.self_times` over events keyed by their
        start, so each self time finds its operation again."""
        if self._ops is None:
            events = self._inside(self.plane.line(OPS_LINE)) \
                if self.plane is not None else []
            keyed = [((mid, s), s, e) for mid, s, e in events]
            self._ops = [(mid, s, t) for (mid, s), t in self_times(keyed)]
        return self._ops

    def runs(self, pattern: str):
        """(start, end) of the whole runs of a program in the window: the
        trace's edges cut the first and the last run short, and a cut run
        is shorter than the median by more than a hundredth."""
        if self.plane is None:
            return []
        rx = re.compile(pattern)
        hits = [(s, e) for mid, s, e in
                self._inside(self.plane.line(MODULES_LINE))
                if rx.search(self.plane.event_names.get(mid, ""))]
        if not hits:
            return []
        median = statistics.median(e - s for s, e in hits)
        return [(s, e) for s, e in hits if e - s >= 0.99 * median]

    def by_scope(self, pattern: str = None):
        """{(component, phase): seconds}. With a program's pattern: per
        step, over that program's whole runs (operations are counted to
        the run they start in); without: over the window. The component
        of an operation outside every program scope is UNSCOPED."""
        ops = self.ops()
        steps = 1
        if pattern is not None:
            runs = self.runs(pattern)
            steps = len(runs)
            if not steps:
                return {}
            los = [lo for lo, _hi in runs]
            ops = [(mid, s, t) for mid, s, t in ops
                   if (i := bisect.bisect_right(los, s) - 1) >= 0
                   and s <= runs[i][1]]
        out = defaultdict(float)
        for mid, _s, t in ops:
            _prog, component, phase = self.scope(mid)
            out[component or UNSCOPED, phase] += t / steps
        return dict(out)

    def step_ms(self, pattern: str, match=None, phases=PHASES):
        """Milliseconds per step of the program's operations in the
        given phases and, with `match`, in a component it accepts. None
        without a whole run, and where `match` finds no operation at all
        (a program that does not scope its layers)."""
        table = self.by_scope(pattern)
        hits = [t for (component, phase), t in table.items()
                if phase in phases and (match is None or (
                    component != UNSCOPED and match(component)))]
        if not table or (match is not None and not hits):
            return None
        return 1e3 * sum(hits)

    def unscoped_share_pct(self):
        """Share of chip 0's busy time in the window whose operation
        carries no scope of the program, or None."""
        table = self.by_scope()
        total = sum(table.values())
        if not total:
            return None
        return 100.0 * sum(t for (c, _p), t in table.items()
                           if c == UNSCOPED) / total

    def unscoped_by_category(self):
        """{hlo_category: seconds} of the window's unscoped operations:
        what runs outside every scope, named by what XLA says it is."""
        out = defaultdict(float)
        for mid, _s, t in self.ops():
            if not self.scope(mid)[1]:
                out[self.plane.event_stats.get(mid, {}).get(
                    "hlo_category", "?")] += t
        return dict(out)

    # -- host -------------------------------------------------------------------
    def spans(self):
        """[(name, start, end, self seconds)] of the program's spans, in
        the window, by start."""
        out = []
        for plane in self.host:
            for _line, events in plane.lines:
                mine = [(plane.event_names.get(mid, ""), s, e)
                        for mid, s, e in events]
                mine = self._inside(
                    [ev for ev in mine if ev[0].startswith(SPAN_PREFIXES)])
                keyed = [((n, s, e), s, e) for n, s, e in mine]
                out += [(n, s, e, t) for (n, s, e), t in self_times(keyed)]
        return sorted(out, key=lambda ev: ev[1])

    def span_stats(self):
        """{name: (count, median seconds, summed self seconds)}."""
        by = defaultdict(list)
        for name, s, e, t in self.spans():
            by[name].append((e - s, t))
        return {n: (len(v), statistics.median(d for d, _t in v),
                    sum(t for _d, t in v)) for n, v in by.items()}

    def span_median_s(self, name: str):
        stats = self.span_stats().get(name)
        return stats[1] if stats else None

    def dispatch_offsets_s(self, pattern: str,
                           span: str = "train_step.dispatch"):
        """For each whole run of the program, its start on the device
        less the start of the nearest `span` on the host. Where the
        device was idle at the dispatch (a loop that reads each result
        before the next call) that is the dispatch's latency plus the
        offset between the two planes' clocks, and it can be negative;
        where calls queue ahead of the device it is neither."""
        starts = sorted(s for n, s, _e, _t in self.spans() if n == span)
        if not starts:
            return []
        return [lo - min(starts, key=lambda s: abs(lo - s))
                for lo, _hi in self.runs(pattern)]


def of(run):
    """The traced run's ScopedTrace, made once for all its readers, or
    None where the trace holds no TPU plane (a CPU rehearsal)."""
    if not hasattr(run, "scoped"):
        run.scoped = None
        if run.trace_summary.device_ops:
            run.scoped = ScopedTrace.from_file(run.tracer.xplane(),
                                               run.trace_summary)
    return run.scoped
