"""Share of set-up that the program's own record names: the union, on
the host's clock, of every phase of `perf.setup_record()` (the package's
import, the model's and the optimizer's construction, `TrainStep`'s, and
the step's first call: its trace, lowering, backend and first run) and of
every program `perf.program_log()` saw JAX trace, lower, compile or load,
over the seconds between the process's start (`run.t_process`) and the
window's first instant (`run.window["t0"]`).

Both ends are `time.perf_counter` readings, as the record's are; only
what lies between them counts, and what overlaps (a program built inside
`build.params`) counts once. The remainder is what no layer names: the
runtime's start, `jax`'s own import before the package's, and the
harness's Python between the program's phases, which
`benchmarks/tools/setup_table.py` lists by position.

The five other set-up readers (`setup_import_s.train`,
`setup_build_s.train`, `setup_other_programs_s.train`,
`step_trace_s.train`, `step_first_run_s.train`) take their helpers from
here. Each returns None on a CPU rehearsal and on a program that keeps
no such record."""
from harness import trace_scopes

STEP_FAMILY = "train_step"      # what TrainStep's CompileTimed is called


def records(run):
    """(set-up record, program rows) of the traced run's process, or
    None: a CPU rehearsal, or a program without the record."""
    from paddle_tpu.observability import perf
    if not hasattr(perf, "setup_record") or trace_scopes.of(run) is None:
        return None
    return perf.setup_record(), perf.program_log()["rows"]


def between(run, intervals):
    """The parts of `intervals` between the run's two ends."""
    lo, hi = run.t_process, run.window["t0"]
    cut = ((max(t0, lo), min(t1, hi)) for t0, t1 in intervals)
    return [(t0, t1) for t0, t1 in cut if t1 > t0]


def union_s(intervals) -> float:
    """Seconds the intervals cover, each instant once."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


def stretches(setup: dict, names=None):
    """(start, end) of every time a phase (one of `names`, or any) ran
    with no phase around it."""
    return [stretch for name, phase in setup.items()
            if names is None or name in names
            for stretch in phase["stretches"]]


def phases_s(run, names):
    """Seconds between the run's ends that the phases `names` cover, or
    None where `records` finds nothing to read."""
    found = records(run)
    if found is None:
        return None
    return union_s(between(run, stretches(found[0], names)))


def programs(rows, step=None):
    """(start, end) of every row, or of the rows that are the step's (by
    their family, never by a function's name), or of those that are not."""
    return [(r.t - r.seconds, r.t) for r in rows
            if step is None or (r.family == STEP_FAMILY) == step]


def read(run):
    found = records(run)
    if found is None:
        return None
    setup, rows = found
    named = union_s(between(run, stretches(setup) + programs(rows)))
    return 100.0 * named / (run.window["t0"] - run.t_process)
