"""Seconds of set-up importing the package: the phase `import` of
`paddle_tpu.observability.perf.setup_record()`, top to bottom of
`paddle_tpu/__init__.py`. Under `benchmarks/run.py` `jax` is imported
before it (`place_caches`), so `jax`'s own import is the gap before this
phase, not in it."""


def read(run):
    return run.spec.module("layer_metrics", "setup_named_share.train") \
        .phases_s(run, ("import",))
