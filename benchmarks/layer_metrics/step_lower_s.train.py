"""Seconds of set-up spent tracing and lowering the step's program:
`paddle_tpu.observability.perf.compile_record("train_step")`, which
`CompileTimed` writes at the step's first call."""
from harness import trace_scopes


def compile_seconds(run, part: str):
    from paddle_tpu.observability import perf
    record = getattr(perf, "compile_record", None)
    if record is None or trace_scopes.of(run) is None:
        return None     # a program without the counter; a CPU rehearsal
    return (record("train_step") or {}).get(part)


def read(run):
    return compile_seconds(run, "lower_s")
