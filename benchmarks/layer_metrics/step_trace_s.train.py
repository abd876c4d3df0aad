"""Seconds of set-up tracing the step's program to a jaxpr: the
`trace_s` of the record `step_lower_s.train` reads, which `CompileTimed`
times around `jit_fn.trace(*args)`. `step_lower_s.train` less this is
the jaxpr's lowering to MLIR alone (the Mosaic lowering of every Pallas
call in it); `compile_record("train_step")["trace_by_scope"]` says which
layers and kernels hold these seconds
(`benchmarks/tools/setup_table.py`)."""


def read(run):
    return run.spec.module("layer_metrics", "step_lower_s.train") \
        .compile_seconds(run, "trace_s")
