"""A cell's set-up as the program tells it, for people:

    python3 benchmarks/tools/setup_table.py --workload <cell> --seed <n>

Sets up as the cell's run does (its driver's `build_step`, the first
steps `correct` reads, one more), on the chip, and prints
`paddle_tpu.observability.perf`'s records: set-up on the clock from the
process's start (every phase that ran with none around it, and between
them the gaps, each with the programs JAX built or loaded in it); the
phases with their seconds and self seconds; the step's first call (its
trace apart from its lowering) and the trace by layer and kernel
(`trace_by_scope`); every program by name. The table also goes to
`chiprun_out/setup_table.<cell>.txt` and the records, whole, to
`chiprun_out/setup_table.jsonl`. PERF.md's set-up tables are this
output."""
import time

T_PROCESS = time.perf_counter()     # as benchmarks/run.py takes it

import argparse     # noqa: E402
import collections  # noqa: E402
import json         # noqa: E402
import os           # noqa: E402

import _common      # noqa: E402

GAP_S = 0.05        # a gap shorter than this has no line of its own
TOP = 20            # scopes and programs printed before "the rest"


def clock_lines(setup, rows, marks, t_start, t_end):
    """Set-up in clock order: [(seconds from start, seconds, text)].
    `marks` are the tool's own instants, [(t, text)]."""
    lines = [(t - t_start, 0.0, f"-- {text}") for t, text in marks]
    stretches = sorted((t0, t1, name) for name, phase in setup.items()
                       for t0, t1 in phase["stretches"]
                       if t_start <= t0 and t1 <= t_end)
    at = t_start
    for t0, t1, name in stretches + [(t_end, t_end, None)]:
        if t0 - at >= GAP_S:
            lines.append((at - t_start, t0 - at,
                          "(gap) " + programs_in(rows, at, t0)))
        if name is not None:
            lines.append((t0 - t_start, t1 - t0, name))
        at = max(at, t1)
    return sorted(lines, key=lambda line: line[0])


def programs_in(rows, t0, t1):
    """What JAX built or loaded in a gap, by name."""
    inside = [r for r in rows if t0 <= r.t - r.seconds and r.t <= t1
              and r.kind != "load"]
    if not inside:
        return "no program"
    by_name = collections.Counter()
    for r in inside:
        by_name[r.fun_name] += r.seconds
    top = ", ".join(f"{name} {s:.2f}" for name, s in by_name.most_common(3))
    return (f"{len(inside)} events of {len(by_name)} programs, "
            f"{sum(by_name.values()):.2f} s: {top}")


def phase_lines(setup):
    """[(phase, entries, seconds, self seconds, parent, counters)]; a
    phase's self time is its seconds less its children's."""
    inside = collections.defaultdict(float)
    for name, phase in setup.items():
        if phase["parent"] not in (None, name):
            inside[phase["parent"]] += phase["s"]
    keys = {"s", "n", "t0", "t1", "parent", "stretches"}
    return [(name, phase["n"], phase["s"], phase["s"] - inside[name],
             phase["parent"] or "-",
             {k: v for k, v in phase.items() if k not in keys})
            for name, phase in sorted(setup.items(),
                                      key=lambda kv: kv[1]["t0"])]


def program_lines(rows, family):
    """[(name, the step's or not, {kind: (n, s)}, seconds)] by seconds."""
    by_name = collections.defaultdict(
        lambda: collections.defaultdict(lambda: [0, 0.0]))
    for r in rows:
        cell = by_name[r.fun_name, r.family == family][r.kind]
        cell[0] += 1
        cell[1] += r.seconds
    out = []
    for (name, steps), kinds in by_name.items():
        seconds = sum(s for kind, (_n, s) in kinds.items() if kind != "load")
        out.append((name, steps, {k: tuple(v) for k, v in kinds.items()},
                    seconds))
    return sorted(out, key=lambda line: -line[3])


def render(cell, seed, device, setup, record, log, marks, t_start, t_end,
           family="train_step"):
    rows = [r for r in log["rows"] if t_start <= r.t <= t_end]
    out = [f"== set-up of {cell}, seed {seed}, on {device}: "
           f"{t_end - t_start:.2f} s from process start to the end of the "
           f"last set-up step"]
    out.append("-- on the clock (at s, for s): phases that ran with none "
               "around them, gaps of %.2f s and more, the tool's marks"
               % GAP_S)
    for at, seconds, text in clock_lines(setup, rows, marks, t_start, t_end):
        out.append(f"{at:9.3f} {seconds:9.3f}  {text}")
    out.append("-- phases (entries, s, self s, inside)")
    for name, n, s, own, parent, counts in phase_lines(setup):
        more = " ".join(f"{k}={v}" for k, v in counts.items())
        out.append(f"{name:24s} {n:6d} {s:9.3f} {own:9.3f}  {parent} {more}")
    if record:
        trace = record.get("trace_s")
        out.append(
            "-- the step's first call: lower_s %.3f = trace_s %s + lowering "
            "%s; backend_s %.3f (%s); first_run_s %.3f" % (
                record["lower_s"],
                "absent" if trace is None else f"{trace:.3f}",
                "-" if trace is None else f"{record['lower_s'] - trace:.3f}",
                record["backend_s"], record["outcome"],
                record["first_run_s"]))
        scopes = sorted(record.get("trace_by_scope", {}).items(),
                        key=lambda kv: -kv[1])
        out.append("-- the trace by scope (self s): %d scopes, %.3f s of "
                   "the trace's %s" % (
                       len(scopes), sum(s for _k, s in scopes),
                       "-" if trace is None else f"{trace:.3f}"))
        for key, s in scopes[:TOP]:
            out.append(f"{s:9.3f}  {key}")
        if scopes[TOP:]:
            out.append(f"{sum(s for _k, s in scopes[TOP:]):9.3f}  "
                       f"({len(scopes) - TOP} more)")
    lines = program_lines(rows, family)
    other = [line for line in lines if not line[1]]
    out.append("-- programs by name (s without the loads inside backend): "
               "%d other than the step's, %.3f s; the step's %.3f s; "
               "functions traced inside another's trace: %d, %.3f s" % (
                   len(other), sum(line[3] for line in other),
                   sum(line[3] for line in lines if line[1]),
                   log["totals"]["traced_inside"]["n"],
                   log["totals"]["traced_inside"]["s"]))
    for name, steps, kinds, seconds in lines[:TOP]:
        parts = " ".join(f"{kind} {n}x {s:.3f}"
                         for kind, (n, s) in sorted(kinds.items()))
        out.append(f"{seconds:9.3f}  {name}{' (the step)' if steps else ''}"
                   f": {parts}")
    if lines[TOP:]:
        out.append(f"{sum(line[3] for line in lines[TOP:]):9.3f}  "
                   f"({len(lines) - TOP} more)")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    spec, cell, cfg, mix, ref = _common.start(args.workload)
    from harness import runlib
    from paddle_tpu.observability import perf

    driver = spec.module("drivers", mix["driver"])
    marks = [(time.perf_counter(), "the chip is there; build_step")]
    step = driver.build_step(cfg, args.seed, ref)
    marks.append((time.perf_counter(), "the first steps correct reads"))
    driver.first_steps(step, cfg, mix, args.seed, ref, ref.CHECK_STEPS)
    marks.append((time.perf_counter(), "one more step"))
    float(step(*driver.batch(cfg, mix, args.seed, ref.CHECK_STEPS)).numpy())
    t_end = time.perf_counter()

    setup, log = perf.setup_record(), perf.program_log()
    record = perf.compile_record("train_step")
    table = render(cell["name"], args.seed, runlib.device_info()["kind"],
                   setup, record, log, marks, T_PROCESS, t_end)
    print(table, flush=True)
    # the table, and the records whole for whoever reads further (not
    # `_common.say`: the rows would push the table out of the end of a
    # chip call's output)
    out_dir = os.path.join(_common.REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"setup_table.{cell['name']}.txt"),
              "a") as f:
        f.write(table + "\n")
    with open(os.path.join(out_dir, "setup_table.jsonl"), "a") as f:
        f.write(json.dumps(dict(
            cell=cell["name"], seed=args.seed, t_process=T_PROCESS,
            t_end=t_end, marks=marks, setup=setup, compile_record=record,
            totals=log["totals"], rows=[list(r) for r in log["rows"]]))
            + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
