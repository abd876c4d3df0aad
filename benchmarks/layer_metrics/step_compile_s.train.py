"""Seconds of set-up spent in the backend's compile of the step's
program: the `backend_s` of the record `step_lower_s.train` reads.

That is XLA's compile when the persistent cache misses and the cache's
load when it hits (49 s against 3.5 s at GPT-3 1.3B, PERF.md section 5,
PR 25), so two readings compare only with the cache in the same state:
the record's `outcome` says which it was, and a traced run that follows
the untraced runs of the same program on one machine finds it warm."""


def read(run):
    return run.spec.module("layer_metrics", "step_lower_s.train") \
        .compile_seconds(run, "backend_s")
