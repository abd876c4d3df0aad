"""What a four-chip trace holds of the experts' exchange, chip by chip:
the operations under the exchange's scopes by name, the seconds a step
that a transfer may be under way (`harness/trace_chips.py`), and the
collective-looking operations that carry no scope of the program (the
TPU compiler's fused reduce-scatters lose theirs), by category and name.

    python3 benchmarks/tools/exchange_table.py <file.xplane.pb> [program]"""
import sys
import types
from collections import defaultdict

import _common  # noqa: F401  (puts the checkout on the path)


def main(argv):
    from harness import trace_chips
    from harness.trace_reduce import Trace, short_name
    run = types.SimpleNamespace(
        trace_summary=Trace.from_file(argv[1]),
        tracer=types.SimpleNamespace(xplane=lambda: argv[1]))
    program = argv[2] if len(argv) > 2 else "jit_step"
    for i, chip in enumerate(trace_chips.of(run)):
        steps = max(len(chip.runs(program)), 1)
        print(f"chip {i} ({chip.plane.name}): {steps} whole runs; exchange "
              f"{trace_chips.exchange_ms(chip, program)} ms a step (under "
              f"its scopes {chip.step_ms(program, trace_chips.in_exchange)}), "
              f"in flight {trace_chips.in_flight_s(chip, program)} s a step")
        scoped, loose = defaultdict(float), defaultdict(float)
        for mid, _s, t in chip.ops():
            name = short_name(chip.plane.event_names.get(mid, ""))
            stats = chip.plane.event_stats.get(mid, {})
            component = chip.scope(mid)[1]
            stem = name.split(".")[0]
            if trace_chips.in_exchange(component):
                scoped[stem, component.rsplit("/", 2)[-1]] += t
            elif not component:
                loose[stats.get("hlo_category", "?"), stem] += t
        for table, what in ((scoped, "under the exchange's scopes"),
                            (loose, "unscoped")):
            print(f"  {what} (ms a step):")
            for key, t in sorted(table.items(), key=lambda kv: -kv[1])[:25]:
                print(f"    {1e3 * t / steps:9.3f}  {key}")


if __name__ == "__main__":
    main(sys.argv)
