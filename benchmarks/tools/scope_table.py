"""Reads a profiler trace by the program's own names: device time per
step by `jax.named_scope` component and phase, the program's spans with
their self times, and the share of the device's busy time that carries
no scope of the program (`harness/trace_scopes.py`).

    python3 benchmarks/tools/scope_table.py <file.xplane.pb> [program] [depth]

`program` is a pattern for the step's program on the `XLA Modules` line
(default `jit_step`; `.` for every operation in the window, not per
step); `depth` cuts the component paths to so many elements and sums
the members of a list (default 6: `gptforcausallm/gpt/layers/*/attn/
qkv_proj`; 0 for whole paths, each block its own row). Any session's trace
will do: `jax.profiler.start_trace`, `paddle_tpu.profiler.Profiler`, or
the benchmark's `--trace 1` run (`.jax_cache/bench_trace/`)."""
import statistics
import sys
from collections import defaultdict

import _common  # noqa: F401  (puts the checkout on the path)


def table(scoped, program: str, depth: int) -> str:
    from harness.trace_scopes import PHASES, UNSCOPED
    per_step = program != "."
    by = scoped.by_scope(program if per_step else None)
    runs = scoped.runs(program) if per_step else []
    rows = defaultdict(lambda: dict.fromkeys(PHASES, 0.0))
    for (component, phase), seconds in by.items():
        if depth and component != UNSCOPED:
            component = "/".join("*" if e.isdigit() else e for e in
                                 component.split("/")[:depth])
        rows[component][phase] += 1e3 * seconds
    total = sum(sum(r.values()) for r in rows.values())
    out = []
    if per_step:
        step_ms = 1e3 * sum(e - s for s, e in runs) / max(len(runs), 1)
        out.append(f"device time per step of {program!r}: {len(runs)} "
                   f"whole runs of {step_ms:.3f} ms; components and "
                   f"the unscoped remainder sum to {total:.3f} ms")
    else:
        out.append(f"device time in the window: {total:.3f} ms")
    out.append(f"{'ms':>10} {'%':>6}  " + " ".join(
        f"{p:>10}" for p in PHASES) + "  component")
    for component, r in sorted(rows.items(),
                               key=lambda kv: -sum(kv[1].values())):
        ms = sum(r.values())
        out.append(f"{ms:10.3f} {100 * ms / total if total else 0:6.2f}  "
                   + " ".join(f"{r[p]:10.3f}" for p in PHASES)
                   + f"  {component}")
    share = scoped.unscoped_share_pct()
    if share is not None:
        kinds = scoped.unscoped_by_category()
        whole = sum(kinds.values())
        out.append(f"unscoped share of the window's busy time: "
                   f"{share:.2f} %, by XLA's category: " + ", ".join(
                       f"{k} {share * t / whole:.2f}" for k, t in sorted(
                           kinds.items(), key=lambda kv: -kv[1])[:5]))
    stats = scoped.span_stats()
    if stats:
        out.append(f"{'count':>6} {'median ms':>10} {'self ms':>10}  span")
        for name, (n, median, self_s) in sorted(stats.items()):
            out.append(f"{n:6d} {1e3 * median:10.3f} {1e3 * self_s:10.3f}"
                       f"  {name}")
    offsets = scoped.dispatch_offsets_s(program) if per_step else []
    if offsets:
        out.append(f"a run starts {1e3 * statistics.median(offsets):+.3f} "
                   f"ms (median of {len(offsets)}) from the start of the "
                   f"nearest train_step.dispatch span: latency and clock "
                   f"offset where the device was idle at the dispatch")
    return "\n".join(out)


def main(argv):
    if len(argv) < 2:
        sys.exit(__doc__)
    from harness.trace_scopes import ScopedTrace
    program = argv[2] if len(argv) > 2 else "jit_step"
    depth = int(argv[3]) if len(argv) > 3 else 6
    print(table(ScopedTrace.from_file(argv[1]), program, depth))


if __name__ == "__main__":
    main(sys.argv)
