"""Performance observability: XLA cost-model telemetry, roofline
accounting and the dispatch-gap profiler (see README "Performance
observability").

ROADMAP item 4 names two measured ceilings (flash fwd at ~1/8.6 of
matmul efficiency, eager/TrainStep dispatch at 1.74 vs the <=1.5
target) but until this module the repo had no STANDING instrumentation
saying where a step's time goes relative to what the hardware allows:
`cost_analysis()` was called ad hoc in tools and thrown away. Three
sub-surfaces, all near-zero when observability is disabled:

* **Cost-model telemetry.** `read_cost_model(compiled)` is the ONE
  reader over XLA's `cost_analysis()` / `memory_analysis()` (callers
  use it instead of re-parsing the dict shapes). Every compile
  that goes through `CompileTimed` (engine ragged/decode executables,
  the TrainStep) or the fused optimizer's AOT path records its
  expected work as gauges, keyed by the same compile families the
  PR 4 compile counters use:
  `paddle_tpu_executable_flops{family=}` and
  `paddle_tpu_executable_bytes{family=,kind=accessed|output|temp|
  argument}` (the most recently compiled executable of the family —
  gauge semantics; per-executable expectations stay on the
  `CompileTimed.expected` handles for tools).

* **Roofline accounting.** `observe_roofline(family, seconds, cost)`
  turns a measured launch/step latency plus the recorded cost model
  into achieved flops/s and bytes/s and publishes them against the
  device peaks as `paddle_tpu_roofline_utilization{family=,
  bound=hbm|flops}`. Peaks come from the per-chip spec tables below;
  an UNKNOWN device (the CPU test box) gets NO
  roofline series — an honest absence beats a made-up denominator.
  Spec peaks are the denominator by convention; `set_device_peaks()`
  lets a test or a session pin another one.

* **Dispatch-gap profiler.** The eager autograd engine
  (`autograd.tape.run_backward`) reports the host-side gap between
  consecutive grad-node dispatches into
  `paddle_tpu_dispatch_gap_seconds` (fine sub-millisecond buckets)
  and attributes each gap to the op type about to be dispatched via
  `paddle_tpu_dispatch_gap_op_seconds_total{op=}` — so the 1.74
  eager-over-TrainStep ratio decomposes into NAMED host gaps before
  anyone tries to batch them. Single flag check per node when
  observability is off.

* **Set-up, told from inside the program.** `setup_record()` is the
  process's set-up by phase (`import`, `build.model`, `build.params`,
  `build.optimizer`, `build.train_step`, and `<family>.lower` /
  `.trace` / `.backend` / `.first_run` of every `CompileTimed` first
  call), each phase's seconds, entries and first and last instant on
  `time.perf_counter`, the clock a caller takes its own marks on; a
  phase is also a `setup.<phase>` span (a first call's keep their
  older names, `compile.<part>`). `program_log()` is every program JAX
  traced, lowered, compiled or loaded from its persistent cache, by
  JAX's own duration events, each row with the function's name, the
  compile family, the phase and the `TrainStep` step it fell into.
  `compile_record(family)["trace_by_scope"]` says which layers and
  kernels the first call's trace spent its seconds in. All three are
  one-shots at build and compile time, written metrics on or off; a
  warm step enters no phase and fires no event.

What reads this module now: `tools/obs_top.py` and the fleet's capacity
lines read the gauges; the benchmark's `step_lower_s.train`,
`step_compile_s.train`, `step_trace_s.train` and
`step_first_run_s.train` read `compile_record()`; its
`setup_import_s.train`, `setup_build_s.train`,
`setup_other_programs_s.train` and `setup_named_share.train` read
`setup_record()` and `program_log()` between the process's start and
the window's first instant, and `benchmarks/tools/setup_table.py`
prints all three for a cell. The benchmark's own utilizations come from
the device trace and `benchmarks/harness/peaks.json`, not from the
host-clock gauges here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import sys
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from jax import monitoring as _monitoring

from . import metrics as _m
from . import tracing as _t

__all__ = [
    "CostModel", "read_cost_model", "CompileTimed", "record_compile",
    "compile_record", "trace_note", "setup_phase", "setup_record",
    "program_log", "ProgramRow", "trace_timed", "trace_timed_call",
    "STEP_CALLS",
    "observe_roofline", "note_dispatch_gap", "note_dispatch_batch",
    "note_graph_cache", "device_peaks", "set_device_peaks", "lookup",
    "interconnect_peaks", "set_interconnect_peaks",
    "PEAK_BF16_FLOPS", "HBM_BYTES_PER_SEC",
    "ICI_BYTES_PER_SEC", "DCN_BYTES_PER_SEC",
    "DISPATCH_GAP_BUCKETS",
]

# ---------------------------------------------------------------------------
# device peaks, per jax device, keyed by the `device_kind` string the
# installed runtime reports (jax 0.9.0 / libtpu 0.0.34; the strings
# were read from `jax.experimental.topologies.get_topology_desc`).
# Only generations where one jax device is one chip are listed: a
# per-chip figure under a per-core device would be a wrong denominator.
# A device kind that is not a key has NO peaks: the roofline gauges
# publish nothing for it.
#
# Sources. "TPU v5 lite" (v5e): Google Cloud documentation, "TPU v5e" —
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip
# interconnect. "TPU v5" (v5p) and "TPU v6 lite" (v6e): the same
# documentation's pages for those versions as copied into this table
# by earlier PRs; not re-checked against a chip, none was available.
# DCN figures are a host NIC's ~25 GB/s split over the host's chips —
# an estimate, not a published peak.
# ---------------------------------------------------------------------------
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12, "TPU v5": 459e12, "TPU v6 lite": 918e12,
}

HBM_BYTES_PER_SEC = {
    "TPU v5 lite": 819e9, "TPU v5": 2765e9, "TPU v6 lite": 1640e9,
}

# per-chip aggregate ONE-WAY interconnect bandwidth. The collective
# observability layer (observability.comms) reads these the way the
# roofline gauges read the HBM table: an unknown device publishes no
# link-utilization series, and algorithmic bandwidth stands alone as
# an absolute gauge. These are link peaks, not what a congested fabric
# delivers.
ICI_BYTES_PER_SEC = {
    "TPU v5 lite": 2.0e11, "TPU v5": 5.4e11, "TPU v6 lite": 3.6e11,
}

DCN_BYTES_PER_SEC = {
    "TPU v5 lite": 3.1e9, "TPU v5": 6.2e9, "TPU v6 lite": 3.1e9,
}


def lookup(device, table: dict):
    """The table's entry for the device's `device_kind`, or None when
    the kind is not a key. Exact match: "TPU v5" must not answer for
    "TPU v5 lite"."""
    return table.get(getattr(device, "device_kind", None))


# operator/test override: (peak_flops, peak_bytes_per_sec) or None
_PEAK_OVERRIDE: Optional[Tuple[float, float]] = None


def set_device_peaks(flops: Optional[float] = None,
                     bytes_per_sec: Optional[float] = None) -> None:
    """Pin the roofline denominators explicitly — for tests on the CPU
    box (which otherwise publishes no roofline series) and for sessions
    that measured their own peaks. Call with no arguments to clear the
    override."""
    global _PEAK_OVERRIDE
    if flops is None and bytes_per_sec is None:
        _PEAK_OVERRIDE = None
    else:
        _PEAK_OVERRIDE = (float(flops or 0.0), float(bytes_per_sec or 0.0))


# operator/test override for the interconnect denominators:
# {"ici": x, "dcn": y} or None
_INTERCONNECT_OVERRIDE: Optional[dict] = None


def set_interconnect_peaks(ici: Optional[float] = None,
                           dcn: Optional[float] = None) -> None:
    """Pin the interconnect peak denominators explicitly (tests on the
    CPU box, sessions that measured their fabric). Call with no
    arguments to clear the override."""
    global _INTERCONNECT_OVERRIDE
    if ici is None and dcn is None:
        _INTERCONNECT_OVERRIDE = None
    else:
        _INTERCONNECT_OVERRIDE = {"ici": float(ici or 0.0),
                                  "dcn": float(dcn or 0.0)}


def interconnect_peaks(device=None) -> Optional[dict]:
    """{"ici": bytes/s, "dcn": bytes/s} for the backend device, or None
    when the device kind matches no table entry — the collective
    link-utilization gauges publish NOTHING on unknown devices, the
    device_peaks() convention."""
    if _INTERCONNECT_OVERRIDE is not None:
        return _INTERCONNECT_OVERRIDE
    if device is None:
        import jax
        device = jax.devices()[0]
    ici = lookup(device, ICI_BYTES_PER_SEC)
    dcn = lookup(device, DCN_BYTES_PER_SEC)
    if ici is None and dcn is None:
        return None
    return {"ici": ici or 0.0, "dcn": dcn or 0.0}


def device_peaks(device=None) -> Optional[Tuple[float, float]]:
    """(peak_flops, peak_bytes_per_sec) for the backend device, or None
    when the device kind matches no table entry (CPU test boxes,
    unknown accelerators) — the roofline gauges publish NOTHING rather
    than a utilization against a made-up denominator."""
    if _PEAK_OVERRIDE is not None:
        return _PEAK_OVERRIDE
    if device is None:
        import jax
        device = jax.devices()[0]
    flops = lookup(device, PEAK_BF16_FLOPS)
    bw = lookup(device, HBM_BYTES_PER_SEC)
    if flops is None or bw is None:
        return None
    return (flops, bw)


# ---------------------------------------------------------------------------
# cost-model reader (the ONE place the cost_analysis()/memory_analysis()
# dict shapes are known)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class CostModel:
    """XLA's static expectation for one compiled executable: total
    FLOPs and HBM bytes accessed from `cost_analysis()`, buffer-class
    byte sizes from `memory_analysis()` (0.0 where a backend reports
    nothing)."""
    flops: float = 0.0
    bytes_accessed: float = 0.0
    bytes_output: float = 0.0
    bytes_argument: float = 0.0
    bytes_temp: float = 0.0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def read_cost_model(compiled) -> Optional[CostModel]:
    """Read a `jax.stages.Compiled` (or anything with the same
    `cost_analysis`/`memory_analysis` surface) into a CostModel.
    Returns None when the backend reports no cost analysis at all —
    callers treat that as "no expectation recorded", never as zero
    work."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not ca:
        return None
    flops = float(ca.get("flops", 0.0))
    accessed = float(ca.get("bytes accessed", 0.0))
    out = arg = temp = 0.0
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        out = float(getattr(ma, "output_size_in_bytes", 0) or 0)
        arg = float(getattr(ma, "argument_size_in_bytes", 0) or 0)
        temp = float(getattr(ma, "temp_size_in_bytes", 0) or 0)
    return CostModel(flops=flops, bytes_accessed=accessed,
                     bytes_output=out, bytes_argument=arg,
                     bytes_temp=temp)


# ---------------------------------------------------------------------------
# metric handles (created once; the disabled path through every
# recorder below is a single module-flag check)
# ---------------------------------------------------------------------------
# dispatch gaps are host-side tens-of-µs to low-ms events: the default
# latency buckets start at 500 µs and would flatten the distribution
# the profiler exists to resolve
DISPATCH_GAP_BUCKETS = (
    10e-6, 25e-6, 50e-6, 100e-6, 250e-6, 500e-6,
    1e-3, 2.5e-3, 5e-3, 10e-3, 25e-3,
)

_METRICS = None


def _metrics():
    global _METRICS
    if _METRICS is None:
        r = _m.registry()
        _METRICS = {
            "flops": r.gauge(
                "paddle_tpu_executable_flops",
                "XLA cost-model expected FLOPs of the family's most "
                "recently compiled executable (per-executable "
                "expectations live on the CompileTimed handles)",
                ("family",)),
            "bytes": r.gauge(
                "paddle_tpu_executable_bytes",
                "XLA cost/memory-model byte expectations of the "
                "family's most recently compiled executable: accessed "
                "= cost-model HBM traffic, output/temp/argument = "
                "buffer-class sizes from memory_analysis()",
                ("family", "kind")),
            "roofline": r.gauge(
                "paddle_tpu_roofline_utilization",
                "achieved fraction of the device peak over the last "
                "measured launch/step of the family: bound=hbm is "
                "expected-bytes/latency over peak HBM bandwidth, "
                "bound=flops is expected-flops/latency over peak "
                "bf16 FLOP/s (spec peaks; unknown devices publish "
                "no series)",
                ("family", "bound")),
            "gap": r.histogram(
                "paddle_tpu_dispatch_gap_seconds",
                "host-side gap between consecutive grad-node "
                "dispatches in the eager backward engine (queue "
                "bookkeeping, cotangent accumulation, hook firing "
                "between device launches)",
                buckets=DISPATCH_GAP_BUCKETS),
            "gap_op": r.counter(
                "paddle_tpu_dispatch_gap_op_seconds_total",
                "cumulative dispatch-gap seconds attributed to the "
                "grad-node op type about to be dispatched",
                ("op",)),
            "batch": r.histogram(
                "paddle_tpu_dispatch_batch_size",
                "grad nodes per backward dispatch call in the fused "
                "dispatch engine: whole-graph and chain runs observe "
                "their length, per-node degradations (hooks, "
                "unfusable ops) observe 1; the per_node A/B "
                "mode records nothing",
                buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256)),
            "graph_cache": r.counter(
                "paddle_tpu_backward_graph_cache_total",
                "whole-graph backward trace cache outcomes, one per "
                "backward in whole_graph dispatch mode: hit = the "
                "entire grad graph dispatched as one cached fused "
                "executable, miss = one freshly traced fused "
                "executable, bypass = the graph fragmented into "
                "multiple dispatches (host-coupled nodes, degraded "
                "segments) — steady-state O(1) dispatch shows as a "
                "monotonically growing hit count",
                ("outcome",)),
        }
    return _METRICS


# where each compile family's set-up seconds went, for the process's
# life (CompileTimed writes it once per first call, metrics on or off)
_FAMILY_COMPILE: Dict[str, dict] = {}


def compile_record(family: str) -> Optional[dict]:
    """Where the family's first calls spent their time in this process,
    or None before its first: `compiles` (first calls so far) and, summed
    over them, `lower_s` (tracing the program and lowering it), of which
    `trace_s` is the trace to a jaxpr alone, so that `lower_s - trace_s`
    is the jaxpr's lowering to MLIR (absent where the function has no
    `.trace` or it raised), `backend_s`
    (XLA's compile, or the persistent cache's load when it hits; with an
    executable store, the store's load) and `first_run_s` (the first
    execution, not waited for), with the last one's `outcome`
    (compile | disk_hit), whatever the traced code noted of itself
    (`trace_note`) and `trace_by_scope`: the trace's seconds by the
    layer that spent them (self time, under its `jax.named_scope` path
    with layer indices folded: `gpt/layers/*/attn`) or by the Pallas
    kernel (`trace_timed`). Written whether or not metrics are enabled:
    a one-shot at compile time costs the hot path nothing."""
    rec = _FAMILY_COMPILE.get(family)
    if rec is None:
        return None
    rec = dict(rec)
    if "trace_by_scope" in rec:
        rec["trace_by_scope"] = dict(rec["trace_by_scope"])
    return rec


def _note_compile(family: str, parts: dict, outcome: str,
                  notes: Optional[dict] = None,
                  scopes: Optional[dict] = None) -> None:
    rec = _FAMILY_COMPILE.setdefault(family, {
        "compiles": 0, "lower_s": 0.0, "backend_s": 0.0,
        "first_run_s": 0.0})
    rec["compiles"] += 1
    for part, seconds in parts.items():
        rec[part + "_s"] = rec.get(part + "_s", 0.0) + seconds
    rec["outcome"] = outcome
    rec.update(notes or {})
    if scopes:
        by_scope = rec.setdefault("trace_by_scope", {})
        for key, seconds in scopes.items():
            by_scope[key] = by_scope.get(key, 0.0) + seconds


class _TraceNotes(threading.local):
    notes = None    # a dict while a CompileTimed's first call runs here,
    options = None  # and one for what the traced code asks of the compile,
    family = None   # that CompileTimed's family
    step = None     # and the TrainStep step that made the call, if one
    scopes = None   # a dict while that call traces its program
    timed = None    # the innermost open `trace_timed`
    phases = ()     # the open set-up phases, outermost first
    tracing = 0     # functions JAX is tracing here, one inside another
    lowered = None  # the program lowered last: a cache load is its


_TRACE_NOTES = _TraceNotes()


def trace_note(key: str, value: str) -> None:
    """From code that runs while a program is traced: which of its paths
    it took (`head_loss`: `fused, chunks 2` | `whole`, models/lm_head.py).
    The note lands in `compile_record(family)` of the `CompileTimed`
    whose first call is tracing on this thread, under `key`; paths taken
    side by side in one program are joined by `; `. Outside such a call
    it is dropped: a one-shot at trace time, nothing on the hot path."""
    notes = _TRACE_NOTES.notes
    if notes is not None:
        seen = notes.get(key)
        if seen is None:
            notes[key] = value
        elif value not in seen.split("; "):
            notes[key] = f"{seen}; {value}"


def trace_compile_option(key: str, value) -> None:
    """From code that runs while a program is traced: an option for the
    compile of that program (`lowered.compile(compiler_options=...)`),
    where the traced code knows of its own structure what the compiler
    cannot (`scan_passes`, distributed/meta_parallel/recompute.py). The
    `CompileTimed` whose first call is tracing on this thread compiles
    with it and says so in `compile_record(family)["compile_options"]`;
    outside such a call it is dropped, as a `trace_note` is."""
    options = _TRACE_NOTES.options
    if options is not None:
        options[key] = value


class _Timed:
    """One layer's or kernel's stretch of a first call's trace: its self
    time (its seconds less those of the stretches opened inside it) goes
    to the call's `trace_by_scope`."""

    __slots__ = ("key", "path", "outer", "inner", "t0")

    def __init__(self, name: str, path: bool):
        self.key, self.path = name, path

    def __enter__(self):
        th = _TRACE_NOTES
        outer = self.outer = th.timed
        if self.path:
            self.key = "/".join("*" if part.isdigit() else part
                                for part in self.key.split("/"))
            if outer is not None and outer.path:
                self.key = f"{outer.key}/{self.key}"
        self.inner = 0.0
        th.timed = self
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self.t0
        th = _TRACE_NOTES
        th.timed = self.outer
        if self.outer is not None:
            self.outer.inner += seconds
        scopes = th.scopes
        if scopes is not None:
            scopes[self.key] = (scopes.get(self.key, 0.0)
                                + seconds - self.inner)
        return False


_NOT_TIMED = contextlib.nullcontext()


def trace_timed(name: str, path: bool = True):
    """Around what runs while a program is traced: the stretch's self
    time lands in `compile_record(family)["trace_by_scope"]` of the
    `CompileTimed` whose first call is tracing on this thread. A layer's
    `name` is its `jax.named_scope`, and its key the path of the open
    stretches with layer indices folded (`gpt/layers/*/attn`); a Pallas
    kernel's entry gives `path=False` and is keyed by the kernel's
    `name=` wherever it is called. Outside such a trace (an eager call
    never comes here, `nn/layer.py`) it times nothing."""
    if _TRACE_NOTES.scopes is None:
        return _NOT_TIMED
    return _Timed(name, path)


def trace_timed_call(name: str):
    """`trace_timed(name, path=False)` around every call of a function:
    the decorator of a Pallas kernel's entry, under its `jax.jit` so that
    only a call that traces the body is timed."""
    def decorate(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with trace_timed(name, path=False):
                return fn(*args, **kwargs)
        return call
    return decorate


# ---------------------------------------------------------------------------
# set-up by phase, and every program JAX builds or loads
# ---------------------------------------------------------------------------
_LOCK = threading.Lock()
_SETUP: Dict[str, dict] = {}


class setup_phase(contextlib.ContextDecorator):
    """One stretch of set-up, timed where the work is: a context manager
    (or a decorator) that adds to `setup_record()[name]` and is the span
    `setup.<name>` (`span=` gives a first call's parts their older
    names). Phases nest: the record keeps the phase a phase was first
    opened inside (`parent`), and a phase opened inside itself (a
    layer's constructor building its sublayers) counts its entry and no
    seconds twice. `seconds` is the last stretch's, after it closed.
    For one-shots only: it takes a lock and two clock reads."""

    def __init__(self, name: str, span: Optional[str] = None, **attrs):
        self.name = name
        self.span = span or "setup." + name
        self.attrs = attrs
        self.seconds = 0.0

    def __enter__(self):
        th = _TRACE_NOTES
        outer = th.phases
        span = _t.span(self.span, **self.attrs)
        span.__enter__()
        t0 = time.perf_counter()
        with _LOCK:
            rec = _SETUP.get(self.name)
            if rec is None:
                rec = _SETUP[self.name] = _new_phase(
                    t0, outer[-1][0] if outer else None)
            rec["n"] += 1
        th.phases = outer + ((self.name, t0, span),)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        th = _TRACE_NOTES
        *outer, (name, t0, span) = th.phases
        th.phases = tuple(outer)
        self.seconds = t1 - t0
        with _LOCK:
            rec = _SETUP[name]
            rec["t1"] = max(rec["t1"], t1)
            if all(name != open_name for open_name, _t0, _sp in outer):
                rec["s"] += t1 - t0
            if not outer:
                _add_stretch(rec, t0, t1)
        span.__exit__(*exc)
        return False

    def count(self, **counts) -> None:
        """Add to the phase's own counters (`params`, `bytes`)."""
        with _LOCK:
            rec = _SETUP[self.name]
            for key, n in counts.items():
                rec[key] = rec.get(key, 0) + n


PHASE_STRETCHES = 32


def _new_phase(t0: float, parent: Optional[str]) -> dict:
    return {"s": 0.0, "n": 0, "t0": t0, "t1": t0, "parent": parent,
            "stretches": []}


def _add_stretch(rec: dict, t0: float, t1: float) -> None:
    stretches = rec["stretches"]
    if len(stretches) < PHASE_STRETCHES:
        stretches.append((t0, t1))
    else:       # the last one grows: the extent stays, the gaps go
        stretches[-1] = (stretches[-1][0], t1)


def setup_since(name: str, t0: float) -> None:
    """A phase that began at `t0` on `time.perf_counter`, before this
    module could be imported, and ends now: the package's `import`. No
    span: the ring and the profiler's session start after it."""
    t1 = time.perf_counter()
    with _LOCK:
        rec = _SETUP.setdefault(name, _new_phase(t0, None))
        rec["s"] += t1 - t0
        rec["n"] += 1
        rec["t1"] = max(rec["t1"], t1)
        _add_stretch(rec, t0, t1)


def setup_record() -> Dict[str, dict]:
    """The process's set-up as the program saw it: {phase: {`s` seconds
    summed over its entries, `n` entries, `t0` its first entry and `t1`
    its last exit on `time.perf_counter`, `parent` the phase it was
    first opened inside or None, `stretches` the (start, end) of each
    time it was opened with no phase around it (the first
    `PHASE_STRETCHES`; the last one grows after that): what a reader
    lays on the clock, since a phase inside another is covered by it;
    and the phase's own counters}}. Phases:
    `import` (top to bottom of `paddle_tpu/__init__.py`; a `jax` the
    caller imported first is before it), `build.model` (every `Layer`
    constructor, the outermost one's seconds), `build.params` inside it
    (the initialisers' calls, with `params` and `bytes`),
    `build.optimizer` (the accumulators, when they are first asked for),
    `build.train_step` (`TrainStep.__init__`), and `<family>.lower`
    with `<family>.trace` inside it, `<family>.backend` and
    `<family>.first_run` of every `CompileTimed` first call. A phase's
    self time is its seconds less what the phases opened inside it
    cover. A reader lays the record between two marks of its own on the
    same clock and counts what lies between them."""
    with _LOCK:
        return {name: dict(rec, stretches=list(rec["stretches"]))
                for name, rec in _SETUP.items()}


class ProgramRow(NamedTuple):
    """One of JAX's duration events: a function traced to a jaxpr
    (`trace`; the outermost one: the functions traced inside it are
    counted in the totals, as `traced_inside`, and have no rows), a
    jaxpr lowered to a module (`lower`), a module compiled,
    or looked up in the persistent cache and loaded (`backend`), and
    inside that, the cache's load alone (`load`). `t` is the event's end
    on `time.perf_counter` and `seconds` its length, so `t - seconds` is
    its start; `family`, `phase` and `step` are the `CompileTimed`
    family, the set-up phase and the `TrainStep` step it fell into, each
    None where there was none."""
    t: float
    kind: str
    fun_name: Optional[str]
    seconds: float
    family: Optional[str]
    phase: Optional[str]
    step: Optional[int]


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_PROGRAM_KINDS = {
    _TRACE_EVENT: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "load",
}
PROGRAM_LOG_ROWS = 4096
_PROGRAMS: List[ProgramRow] = []
_PROGRAM_TOTALS = {kind: {"n": 0, "s": 0.0}
                   for kind in (*_PROGRAM_KINDS.values(), "traced_inside")}

# {code object of a function that runs one step: the name of its local
# that holds the step's id}: how a program built in the middle of a
# step is told which step that was. `jit/__init__.py` lists
# `TrainStep._call`. The step itself pays nothing to be found: the
# listener looks at the stack, and it runs when JAX builds a program.
STEP_CALLS: Dict[object, str] = {}


def _in_flight(depth: int = 2):
    """(compile family, step id) of the calls on this thread's stack:
    the innermost `CompileTimed.__call__` and the innermost function of
    `STEP_CALLS`, None where there is none."""
    family = None
    frame = sys._getframe(depth)
    while frame is not None:
        code = frame.f_code
        if code is _COMPILE_TIMED_CALL:
            if family is None:
                family = frame.f_locals["self"].family
        elif code in STEP_CALLS:
            return family, frame.f_locals.get(STEP_CALLS[code])
        frame = frame.f_back
    return family, None


def _on_trace_begins(event: str, _value, **_kw) -> None:
    # JAX says when a trace begins (a scalar, its start time) and when
    # it has ended (the duration below): between the two, the functions
    # a model calls are traced into the outer program by the thousand
    if event == _TRACE_EVENT:
        _TRACE_NOTES.tracing += 1


def _on_program(event: str, seconds: float, fun_name=None, **_kw) -> None:
    kind = _PROGRAM_KINDS.get(event)
    if kind is None:
        return
    t = time.perf_counter()
    th = _TRACE_NOTES
    row = None
    if kind == "trace":
        th.tracing = max(th.tracing - 1, 0)
        if th.tracing:
            kind = "traced_inside"      # counted, and no row
    if kind != "traced_inside":
        if fun_name is not None:
            # a module is named after its function: jit(build) is build
            fun_name = str(fun_name)
            if fun_name.startswith("jit(") and fun_name.endswith(")"):
                fun_name = fun_name[4:-1]
        if kind == "lower":
            th.lowered = fun_name
        elif kind == "load":
            fun_name = th.lowered   # the cache's event carries no name
        if th.notes is not None:
            family, step = th.family, th.step
        else:
            family, step = _in_flight()
        phases = th.phases
        row = ProgramRow(t, kind, fun_name, seconds, family,
                         phases[-1][0] if phases else None, step)
    with _LOCK:
        total = _PROGRAM_TOTALS[kind]
        total["n"] += 1
        total["s"] += seconds
        if row is not None and len(_PROGRAMS) < PROGRAM_LOG_ROWS:
            _PROGRAMS.append(row)


def program_log() -> dict:
    """Every program JAX traced, lowered, compiled or loaded in this
    process, by JAX's own duration events: `rows`, the first
    `PROGRAM_LOG_ROWS` `ProgramRow`s in the order the events ended (a
    cache load ends before the `backend` event around it), and `totals`,
    {kind: {`n`, `s`}} over all of them, which go on when the rows are
    full. It answers
    what set-up spent on programs that are not the step's (rows whose
    `family` is not the step's, by name), and which step built a program
    after its family's first call (a row with a `step` and no first-call
    phase: a new input signature, which `CompileTimed` serves through
    the polymorphic function without a word). A warm step fires no
    event, so the listener costs it nothing."""
    with _LOCK:
        return {"rows": list(_PROGRAMS),
                "totals": {kind: dict(total)
                           for kind, total in _PROGRAM_TOTALS.items()}}


def record_compile(family: str, compiled) -> Optional[CostModel]:
    """Read a freshly compiled executable's cost model and (when
    observability is enabled) publish the executable gauges. The read
    happens even while disabled: it is a one-shot at compile time, and
    `CompileTimed.expected` carries the expectation regardless of
    metric recording."""
    cm = read_cost_model(compiled)
    if cm is None:
        return None
    if _m._ENABLED:
        m = _metrics()
        m["flops"].labels(family=family).set(cm.flops)
        b = m["bytes"]
        b.labels(family=family, kind="accessed").set(cm.bytes_accessed)
        b.labels(family=family, kind="output").set(cm.bytes_output)
        b.labels(family=family, kind="argument").set(cm.bytes_argument)
        b.labels(family=family, kind="temp").set(cm.bytes_temp)
    return cm


def observe_roofline(family: str, seconds: float,
                     cost: Optional[CostModel]) -> None:
    """Publish achieved-vs-peak utilization for one measured execution
    (a blocking-timed engine launch, a steady-state train step). No-op
    while observability is disabled; the roofline gauges additionally
    demand a KNOWN device peak (see device_peaks)."""
    if not _m._ENABLED or cost is None or seconds <= 0.0:
        return
    peaks = device_peaks()
    if peaks is None:
        return
    peak_flops, peak_bw = peaks
    m = _metrics()["roofline"]
    if peak_bw > 0:
        m.labels(family=family, bound="hbm").set(
            cost.bytes_accessed / seconds / peak_bw)
    if peak_flops > 0:
        m.labels(family=family, bound="flops").set(
            cost.flops / seconds / peak_flops)


def note_dispatch_gap(seconds: float, op: str) -> None:
    """One host-side inter-dispatch gap from the eager backward engine.
    Callers (autograd.tape) guard on the metrics flag, so this is never
    reached while disabled — the body records unconditionally."""
    m = _metrics()
    m["gap"].observe(seconds)
    m["gap_op"].labels(op=op).inc(seconds)


def note_dispatch_batch(n_nodes: int) -> None:
    """One backward dispatch call of the batched engine covering
    `n_nodes` grad nodes (1 = degraded per-node dispatch). Caller
    guards on the metrics flag like note_dispatch_gap."""
    _metrics()["batch"].observe(n_nodes)


def note_graph_cache(outcome: str) -> None:
    """One whole-graph backward cache outcome (hit|miss|bypass) from
    the dispatch engine, recorded once per backward in whole_graph
    mode. Caller guards on the metrics flag like note_dispatch_gap."""
    _metrics()["graph_cache"].labels(outcome=outcome).inc()


# ---------------------------------------------------------------------------
# first-call compile shim (grew out of the llm_engine-local
# _CompileTimed; now shared by the engine executables and TrainStep)
# ---------------------------------------------------------------------------
class CompileTimed:
    """First-call timing shim around a freshly built jit function.

    The first call goes through the AOT path (`lower(...).compile()`)
    so the compiled executable is IN HAND for cost-model telemetry —
    the wall time of lower+compile+first execution is recorded as the
    family's compile cost (the same quantity the old first-call shim
    measured: jax traced+compiled synchronously inside that call), and
    `record_compile` reads `cost_analysis()`/`memory_analysis()` into
    the executable gauges. Afterwards calls go straight to the compiled
    executable; `expected` carries the CostModel for roofline
    accounting at the call sites.

    The first call's parts are four spans (`compile.lower` and, inside
    it, `compile.trace`: the trace to a jaxpr apart from the jaxpr's
    lowering; `compile.backend`; `compile.first_run`; each with
    `family=`), each a phase of `setup_record()` (`<family>.lower`, ...)
    and their seconds go to `compile_record(family)`, metrics on or off:
    what a run's set-up spent tracing, lowering and compiling (or
    loading) the family's programs.

    Degradation contract: if AOT lowering/compiling raises (an exotic
    backend, a sharding the AOT path rejects) the shim falls back to
    plain jit dispatch — compile count/time still recorded, no cost
    model (`expected` stays None, roofline stays silent). If a LATER
    call hits the compiled executable with a different input signature
    (jit would retrace; AOT raises TypeError before any donation is
    consumed), the shim permanently reverts to the polymorphic jit
    function — correctness first, telemetry only for the signatures it
    saw first.

    Persistent-cache hook: when constructed with `store`/`store_key`
    (an `inference.exec_cache.ExecCache` and its graftlint-audited
    fingerprint digest), the first call consults the store BEFORE
    lowering. A hit deserializes a live executable — no trace, no XLA
    compile — and accounts outcome=disk_hit; a miss compiles as before
    and parks the fresh executable back in the store, outcome=compile.
    A stale disk entry whose signature rejects the very first call is
    discarded on the spot and the call falls through to a fresh
    compile: the store can delay the compile, never substitute a wrong
    executable."""

    __slots__ = ("fn", "jit_fn", "family", "pending", "expected",
                 "store", "store_key", "store_device")

    def __init__(self, fn, family: str, store=None, store_key=None,
                 store_device=None):
        self.fn = fn
        self.jit_fn = fn
        self.family = family
        self.pending = True
        self.expected: Optional[CostModel] = None
        self.store = store
        self.store_key = store_key
        self.store_device = store_device

    def _load_from_store(self):
        if self.store is None or self.store_key is None:
            return None
        try:
            return self.store.load(self.store_key,
                                   device=self.store_device)
        except Exception:
            return None

    def _save_to_store(self, compiled) -> None:
        if self.store is None or self.store_key is None:
            return
        try:
            self.store.save(self.store_key, compiled,
                            family=self.family,
                            device=self.store_device)
        except Exception:
            pass

    def __call__(self, *args):
        if not self.pending:
            if self.fn is self.jit_fn:
                return self.fn(*args)
            try:
                return self.fn(*args)
            except TypeError:
                # new input signature: AOT executables are monomorphic.
                # The mismatch is detected before donation consumes any
                # buffer, so re-dispatching through jit is safe — and if
                # the TypeError was real, jit raises it again. The
                # recorded cost model described the FIRST signature
                # only: drop it so roofline reads go silent instead of
                # silently wrong for the new shapes.
                self.fn = self.jit_fn
                self.expected = None
                return self.fn(*args)
        t0 = time.perf_counter()
        outcome = "compile"
        out = None
        ran = False
        parts = {"lower": 0.0, "backend": 0.0, "first_run": 0.0}
        notes, scopes, options = {}, {}, {}
        th = _TRACE_NOTES
        step = _in_flight(1)[1]

        def timed(part, fn, *a):
            # one part of the first call: a `compile.<part>` span and a
            # phase of the set-up record, its seconds kept for the
            # family's compile_record beside what the traced code noted
            # of itself (`trace_note`, and `trace_timed` while it traces)
            outer = th.notes, th.family, th.step, th.scopes, th.options
            th.notes, th.family, th.step = notes, self.family, step
            if part in ("lower", "trace"):
                th.scopes, th.options = scopes, options
            phase = setup_phase(f"{self.family}.{part}",
                                span="compile." + part, family=self.family)
            try:
                with phase:
                    return fn(*a)
            finally:
                th.notes, th.family, th.step, th.scopes, th.options = outer
                parts[part] = parts.get(part, 0.0) + phase.seconds

        def lower():
            # `jit_fn.lower(*args)` is `.trace(*args).lower()`: the same
            # work in two calls, so that the trace is timed by itself
            trace = getattr(self.jit_fn, "trace", None)
            if trace is not None:
                try:
                    traced = timed("trace", trace, *args)
                except Exception:
                    parts.pop("trace")      # `trace_s` stays absent
                else:
                    return traced.lower()
            return self.jit_fn.lower(*args)

        compiled = None
        if self.store is not None:
            compiled = timed("backend", self._load_from_store)
        if compiled is not None:
            try:
                out = timed("first_run", compiled, *args)
                ran = True
                outcome = "disk_hit"
            except TypeError:
                # stale entry with a mismatched signature (detected
                # before donation consumes anything): discard it and
                # pay the fresh compile below
                compiled = None
        if compiled is None:
            try:
                lowered = timed("lower", lower)
                compile_ = lowered.compile
                if options:     # what the traced code asked for
                    compile_ = functools.partial(
                        compile_, compiler_options=dict(options))
                    notes["compile_options"] = "; ".join(
                        f"{k}={v}" for k, v in sorted(options.items()))
                # a cache load when jax's persistent cache hits
                compiled = timed("backend", compile_)
            except Exception:
                compiled = None     # fall back to plain jit dispatch
            else:
                self._save_to_store(compiled)
        if not ran:
            out = timed("first_run", compiled if compiled is not None
                        else self.jit_fn, *args)
        # cleared only on success: a first call that raises (watchdog,
        # injected fault) leaves the compile un-recorded, and the
        # retry — which pays the compile again or hits jax's cache —
        # records it instead of losing the count
        self.pending = False
        _note_compile(self.family, parts, outcome, notes, scopes)
        if compiled is not None:
            self.fn = compiled
            self.expected = record_compile(self.family, compiled)
        if _m._ENABLED:
            c, h = _m.compile_metrics()
            c.labels(family=self.family, outcome=outcome).inc()
            h.labels(family=self.family).observe(
                time.perf_counter() - t0)
        return out


_COMPILE_TIMED_CALL = CompileTimed.__call__.__code__
_monitoring.register_scalar_listener(_on_trace_begins)
_monitoring.register_event_duration_secs_listener(_on_program)
