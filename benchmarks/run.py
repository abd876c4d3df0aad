"""The benchmark's one command.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX. It needs the TPU chips the
cell asks for and ends without a result when they are not there. It
sets up (weights from the seed, every program the cell's traffic uses
warmed), measures for `--seconds`, checks what the timed path produced
against the configuration's plain reference, and prints the result as
the last line of standard output. `--trace 0` reports the cell's
end-to-end metrics; `--trace 1` profiles a slice of the window and
reports its per-layer metrics and the breakdown.

Everything that belongs to one cell, configuration, traffic mix or
per-layer metric is a file found by the name `BENCHMARK.json` gives it
(see `harness/spec.py`).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up counts from here

import argparse     # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402
import types        # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
CACHE = os.path.join(REPO, ".jax_cache")


def place_caches():
    """Everything a run caches goes to fixed directories inside the
    checkout that git ignores; only the first run of a cell compiles.
    A `JAX_COMPILATION_CACHE_DIR` the machine sets is dropped before JAX
    reads it, so that `use_compile_cache()` takes `<checkout>/.jax_cache`:
    the chip tool's machines set one that is capped and trimmed between
    calls, and with it every write failed and every run compiled
    (PERF.md section 6). The measurements were made without it.

    The kernels' timing sweep is off: every run compiles the kernels at
    their hand-tuned default blocks. With the sweep on, the run that
    tunes compiles other programs than the runs that find the table (on
    the chip the step's cache key differed between the two, PERF.md
    section 6), so a checkout's second run compiled everything again,
    and two checkouts could pick different winners from timing noise."""
    os.environ["PADDLE_TPU_PALLAS_AUTOTUNE"] = "0"
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    os.environ.setdefault("PADDLE_TPU_CACHE_DIR",
                          os.path.join(CACHE, "kernel_tuning"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from paddle_tpu.utils.runtime_env import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv=None, repo: str = REPO, require_chip=True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (repo, BENCH_DIR):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import runlib
    from harness.spec import Spec

    spec = Spec(repo)
    workload = spec.workload(args.workload)
    cell = {**spec.data("cells", workload["name"]), **workload}
    place_caches()
    if require_chip:
        device = runlib.require_tpu(cell["chips"])
    else:
        device = runlib.device_info()

    mix = spec.data("traffic", cell["traffic"])
    ctx = types.SimpleNamespace(
        spec=spec, cell=cell, mix=mix, seed=args.seed,
        seconds=args.seconds, t_process=T_PROCESS,
        cfg=spec.data("configs", cell["config"]),
        ref=spec.module("reference", cell["config"]),
        watch=runlib.WindowWatch(), window=None, trace_summary=None,
        device=device,
        tracer=runlib.TraceSlice(
            bool(args.trace), os.path.join(CACHE, "bench_trace"),
            mix["trace_after_s"], mix["trace_for_s"]))
    out = spec.module("drivers", mix["driver"]).run(ctx)

    device = dict(device, memory_peak_bytes=out["peak"])
    breakdown = None
    if args.trace:
        from harness.trace_reduce import Trace
        path = ctx.tracer.xplane()
        if path is None:
            raise RuntimeError("the traced run left no .xplane.pb")
        ctx.trace_summary = trace = Trace.from_file(path)
        device.update(busy_s=trace.busy_s, window_s=trace.window_s)
        breakdown = trace.breakdown()
        out["notes"]["kernel_calls"] = trace.kernel_calls()
        wanted = spec.metrics("per_layer", cell["name"])
    else:
        wanted = spec.metrics("end_to_end", cell["name"])
    metrics = {}
    for m in wanted:
        if args.trace:
            value = spec.module("layer_metrics", m["name"]).read(ctx)
        else:
            value = out["e2e"].get(m["name"])
        if value is not None:       # a reader with nothing to read
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    runlib.emit(correct=out["correct"], attempted=out["attempted"],
                failed=out["failed"], metrics=metrics, device=device,
                breakdown=breakdown, compared=out["compared"],
                notes=out["notes"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
