"""The knee, found once: one engine, one warm-up, then one ramp and one
window at each of a few fixed mean rates.

    python benchmarks/tools/knee_sweep.py <workload> <seed> <seconds> <mean rate> [<mean rate> ...]

Each rate keeps the mix's burst shape (burst_rate / base_rate, on and off
lengths). A line per rate: the tails, how many finished requests met both
of the mix's limits, the backlog at the window's start and end, and the
tokens per second completed."""
import copy
import sys

import _common


def main():
    workload, seed, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    rates = [float(x) for x in sys.argv[4:]]
    spec, cell, cfg, mix, ref = _common.start(workload)
    sw = spec.module("drivers", "serve_window")
    from harness.runlib import percentile
    engine = sw.build_engine(cfg, seed, ref)
    sw.warm_up(engine, cfg, mix)
    g = mix["generator"]
    shape = g["burst_rate"] / g["base_rate"]
    for rate in rates:
        m = copy.deepcopy(mix)
        base = rate * (g["off_s"] + g["on_s"]) / (
            g["off_s"] + shape * g["on_s"])
        m["generator"].update(base_rate=base, burst_rate=base * shape)
        events = sw.schedule(cfg, m, seed, seconds)
        sw.check_schedule(events, m)
        w = sw.measure(engine, events, m, seconds)
        span = w["t1"] - w["t0"]
        lim = mix["limits_ms"]
        met = [r for r in w["first_in"] if r.last is not None
               and r.last <= w["t1"] and r.n > r.n_first
               and 1e3 * (r.first - r.due) <= lim["ttft"]
               and 1e3 * (r.last - r.first) / (r.n - r.n_first)
               <= lim["tpot"]]
        rows = [s[2] for s in w["steps"]]
        _common.say(
            f"knee.{workload}.jsonl", mean_rate=rate, base_rate=base,
            burst_rate=base * shape, window_s=span, offered=w["offered"],
            finished=len(w["done"]),
            failed=sum(1 for r in w["done"] if not r.ok),
            backlog_start=w["backlog_start"], backlog_end=w["backlog"],
            tok_s=w["tokens"] / span,
            ttft_p50_ms=percentile(w["ttft_ms"], 50),
            ttft_p95_ms=percentile(w["ttft_ms"], 95),
            tpot_p50_ms=percentile(w["tpot_ms"], 50),
            tpot_p95_ms=percentile(w["tpot_ms"], 95),
            met_both=len(met), tpot_samples=len(w["tpot_ms"]),
            rows_mean=sum(rows) / max(len(rows), 1),
            prefix_hit_tokens=w["stats"]["prefix_cache_hit_tokens"],
            prefix_miss_tokens=w["stats"]["prefix_cache_miss_tokens"],
            preemptions=w["stats"]["preemptions"])
        while engine.has_unfinished:     # drain before the next rate
            engine.step()


if __name__ == "__main__":
    main()
