"""Host time of one `TrainStep.__call__`: the median `train_step` span
of the traced slice (its children `train_step.feed` and
`train_step.dispatch` are in `tools/scope_table.py`'s table). The traced
run's host is slowed by the profiler: compare traced with traced."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    seconds = scoped and scoped.span_median_s("train_step")
    return 1e3 * seconds if seconds else None
