"""Prints what a trace holds: planes, lines, and each device line's
operations by summed time — for a look before a reader is written.

    python benchmarks/tools/trace_names.py <file.xplane.pb> [top]"""
import sys
from collections import defaultdict

import _common  # noqa: F401


def main():
    from jax.profiler import ProfileData
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    for plane in ProfileData.from_file(sys.argv[1]).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            by, n, lo, hi = defaultdict(float), 0, None, None
            for ev in line.events:
                by[ev.name] += ev.duration_ns * 1e-9
                n += 1
                lo = ev.start_ns if lo is None else min(lo, ev.start_ns)
                hi = max(hi or 0, ev.start_ns + ev.duration_ns)
            print(f"  LINE {line.name!r}: {n} events, "
                  f"{((hi or 0) - (lo or 0)) * 1e-9:.3f}s from {lo}")
            if plane.name.startswith("/device") or "harness" in str(
                    list(by)[:50]):
                for name, t in sorted(by.items(), key=lambda kv: -kv[1])[:top]:
                    print(f"      {t:9.5f}s  {name[:150]}")


if __name__ == "__main__":
    main()
