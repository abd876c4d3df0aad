"""Perf-ledger trajectory and regression ATTRIBUTION over the
per-family expected/achieved records `bench.py` appends to
`perf_ledger.jsonl` (observability.perf.family_records, one record per
config run).

The round-over-round gate (`bench.py --gate`) answers "did throughput
regress"; this tool answers "WHICH executable family regressed": it
diffs the latest record per config against the ledger history,
comparing each family's achieved bytes/s (the HBM-bound side — every
hot path in this repo is bandwidth-dominated).

    python tools/perf_ledger.py                  # trajectory table
    python tools/perf_ledger.py --check          # diff latest vs history
    python tools/perf_ledger.py --check --tol 0.2 --config decode_paged

`--check` verdict rules (printed as one JSON line, exit 0/1):
  * a family whose achieved rate dropped below (1 - tol) x the best
    PRIOR-REVISION record for the same config FAILS and names the
    family — the attribution the gate cannot give;
  * prior records from the SAME revision only report the ratio (two
    runs of one revision differ by box noise, not by code — the
    interleaved-window gate is the honest same-code comparator), so
    a ledger written
    entirely by the current revision is self-consistent and passes;
  * a family present in every prior record of a config but MISSING
    from the latest fails (an instrumented path silently stopped
    running — the regression observability itself would otherwise
    hide);
  * records carrying a backward dispatch `mode` (bench.py --config
    dispatch writes one per mode: per_node, batched, whole_graph) are
    baselined per (config, mode), and their
    `dispatch_gap.ms_per_step` is checked the same way bytes/s is — a
    latest gap total ABOVE (1 + tol) x the best prior-revision record
    for the same (config, mode) fails, so the fused engines' host-gap
    win cannot silently erode; a whole_graph record's `graph_cache`
    hit/miss/bypass counts ride the record and are echoed in the
    verdict (report-only: steady-state O(1) dispatch shows as hits
    dominating);
  * the dispatch config's whole_graph record also carries the
    training-numerics on-vs-off overhead ratio (`numerics.
    overhead_ratio`, bench.py --config dispatch) — a cost like the
    gap total, checked with the same mirror rule plus an absolute
    floor, so the numerics plane's ≤3% overhead claim cannot silently
    erode; the measured grad norm rides report-only;
  * records carrying a fleet `process_role` (observability.fleet's
    `append_capacity_ledger` writes one per process) are baselined per
    (config, process_role), and their `capacity.req_per_s` /
    `capacity.tok_per_s` follow the bytes/s rule — a role's achieved
    rate dropping below (1 - tol) x the best prior-revision record
    fails, naming the role the elastic scaler is about to mis-size
    from;
  * the router_serving record's `reintegration` block (bench.py's
    cold-vs-warm process-fleet phase over the persistent executable
    store) is a cost mirror: `warm_over_cold` rising above (1 + tol)
    x the best prior-revision ratio AND past an absolute floor fails,
    and `warm_skipped_all_compiles=false` fails outright — a warm
    replacement re-compiling executables it should have disk-loaded
    is the store not working, not a slow box.

Records keep absolute achieved rates, so cross-revision diffs carry
the same box-noise caveat as any non-interleaved comparison — the
verdict names suspects for the gate to re-measure, it does not replace
the gate."""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_ledger_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perf_ledger.jsonl")


def load(path: str):
    """[(lineno, record)] in file order; malformed lines are counted,
    not fatal (a crashed bench append must not wedge the tool)."""
    records, bad = [], 0
    with open(path, encoding="utf-8") as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
                if isinstance(rec, dict) and "families" in rec:
                    records.append((i, rec))
                else:
                    bad += 1
            except ValueError:
                bad += 1
    return records, bad


def _achieved(fam_rec) -> float:
    v = fam_rec.get("achieved_bytes_per_s")
    return float(v) if v else 0.0


def _config_key(rec) -> str:
    """Baseline grouping key: config, suffixed with the backward
    dispatch mode when present — batched and per_node records of the
    dispatch config baseline independently — and with the fleet
    process_role when present (observability.fleet capacity records:
    prefill replicas and decode replicas of one fleet config baseline
    independently, the way dispatch modes do)."""
    config = rec.get("config", "?")
    mode = rec.get("mode")
    role = rec.get("process_role")
    # a DISPLAY label, not an executable-cache key: all components
    # are strings straight from the record, no coercion to hide
    key = f"{config}[{mode}]" if mode else config  # graftlint: disable=unstable-cache-key
    return f"{key}@{role}" if role else key  # graftlint: disable=unstable-cache-key


# a gap delta below this is timer jitter, not a regression — it gives
# the dispatch-gap check a finite threshold even over a 0.0 baseline
GAP_FLOOR_MS_PER_STEP = 0.01

# numerics on-vs-off overhead is a ratio near 1.0 measured on a noisy
# box: require the regression to clear an absolute floor on top of the
# relative tolerance (the GAP_FLOOR idiom) before failing
NUMERICS_OVERHEAD_FLOOR = 0.05


def _numerics_ratio(rec):
    num = rec.get("numerics")
    if not isinstance(num, dict):
        return None
    v = num.get("overhead_ratio")
    return float(v) if v is not None else None


# warm/cold fleet-reintegration is a wall-clock ratio on a noisy box
# (process spawn + RPC + deserialize over a spawn + RPC + compile
# baseline): require the regression to clear an absolute floor on top
# of the relative tolerance, the NUMERICS_OVERHEAD_FLOOR idiom
REINTEGRATION_FLOOR_RATIO = 0.05


def _reint_ratio(rec):
    reint = rec.get("reintegration")
    if not isinstance(reint, dict):
        return None
    v = reint.get("warm_over_cold")
    return float(v) if v is not None else None


def _gap_ms(rec):
    gap = rec.get("dispatch_gap")
    if not isinstance(gap, dict):
        return None
    v = gap.get("ms_per_step")
    return float(v) if v is not None else None


def check(records, tol: float, only_config=None) -> dict:
    """Diff the LATEST record per (config, mode) against that group's
    ledger history. Returns the verdict dict (see module docstring)."""
    by_config = {}
    for _ln, rec in records:
        by_config.setdefault(_config_key(rec), []).append(rec)
    verdict = {"pass": True, "tol": tol, "configs": {}}
    for config, recs in sorted(by_config.items()):
        if only_config and config.split("[", 1)[0].split("@", 1)[0] \
                != only_config:
            continue
        latest = recs[-1]
        # baselines must share the latest record's DEVICE: achieved
        # rates are absolute, and a v5e record is not a regression
        # baseline for a CPU smoke run of the same config
        history = [r for r in recs[:-1]
                   if r.get("device") == latest.get("device")]
        out = {"rev": latest.get("rev"), "history": len(history),
               "families": {}, "missing_families": [], "pass": True}
        for family, fam_rec in sorted(latest["families"].items()):
            cur = _achieved(fam_rec)
            fout = {"achieved_bytes_per_s": cur or None,
                    "ratio_vs_history": None, "baseline_rev": None,
                    "regressed": False}
            # baseline: best prior achieved rate, preferring a
            # DIFFERENT revision (same-rev deltas are box noise)
            prior = [(_achieved(pf), prev.get("rev"))
                     for prev in history
                     for pf in [prev["families"].get(family)]
                     if pf and _achieved(pf)]
            other_rev = [p for p in prior if p[1] != latest.get("rev")]
            best, best_rev = max(other_rev or prior,
                                 default=(None, None))
            if best and cur:
                fout["ratio_vs_history"] = round(cur / best, 4)
                fout["baseline_rev"] = best_rev
                if best_rev != latest.get("rev") \
                        and cur / best < 1.0 - tol:
                    fout["regressed"] = True
                    out["pass"] = False
            out["families"][family] = fout
        if history:
            always = set(history[0]["families"])
            for prev in history[1:]:
                always &= set(prev["families"])
            gone = sorted(always - set(latest["families"]))
            if gone:
                out["missing_families"] = gone
                out["pass"] = False
        # dispatch-gap regression: the gap total is a COST, so the
        # mirror of the bytes/s rule — latest above (1 + tol) x the
        # best (lowest) prior-revision gap for this (config, mode)
        # fails; same-rev priors report-only, same-device only. An
        # absolute floor keeps a 0.0 baseline (the routine batched
        # result: one fused dispatch per backward, zero gaps) from
        # giving the check infinite sensitivity to timer jitter.
        cur_gap = _gap_ms(latest)
        if cur_gap is not None:
            gout = {"ms_per_step": cur_gap, "ratio_vs_history": None,
                    "baseline_rev": None, "regressed": False}
            prior = [(_gap_ms(prev), prev.get("rev"))
                     for prev in history]
            prior = [p for p in prior if p[0] is not None]
            other_rev = [p for p in prior if p[1] != latest.get("rev")]
            pool = other_rev or prior
            if pool:
                best_gap, best_rev = min(pool)
                if best_gap > 0:
                    gout["ratio_vs_history"] = round(
                        cur_gap / best_gap, 4)
                gout["baseline_rev"] = best_rev
                if best_rev != latest.get("rev") and cur_gap > max(
                        best_gap * (1.0 + tol),
                        best_gap + GAP_FLOOR_MS_PER_STEP):
                    gout["regressed"] = True
                    out["pass"] = False
            out["dispatch_gap"] = gout
        # whole-graph trace-cache counts ride along report-only: the
        # steady-state claim (hits dominate) is pinned by tests; here
        # the verdict just keeps the observability next to the gap it
        # explains
        gc = latest.get("graph_cache")
        if isinstance(gc, dict):
            out["graph_cache"] = gc
        # numerics-plane overhead regression (ISSUE 15): the dispatch
        # config's whole_graph record carries the measured numerics
        # on-vs-off step-time ratio — a COST like the gap total, so
        # the same mirror rule: latest above (1 + tol) x the best
        # (lowest) prior-revision ratio AND past an absolute floor
        # fails; same-rev priors report-only, same-device only.
        cur_num = _numerics_ratio(latest)
        if cur_num is not None:
            nout = {"overhead_ratio": cur_num,
                    "ratio_vs_history": None, "baseline_rev": None,
                    "regressed": False,
                    "grad_norm": (latest.get("numerics") or {}).get(
                        "grad_norm")}
            prior = [(_numerics_ratio(prev), prev.get("rev"))
                     for prev in history]
            prior = [p for p in prior if p[0] is not None]
            other_rev = [p for p in prior if p[1] != latest.get("rev")]
            pool = other_rev or prior
            if pool:
                best_num, best_rev = min(pool)
                if best_num > 0:
                    nout["ratio_vs_history"] = round(
                        cur_num / best_num, 4)
                nout["baseline_rev"] = best_rev
                if best_rev != latest.get("rev") and cur_num > max(
                        best_num * (1.0 + tol),
                        best_num + NUMERICS_OVERHEAD_FLOOR):
                    nout["regressed"] = True
                    out["pass"] = False
            out["numerics"] = nout
        # fleet warm-reintegration regression (router_serving's
        # process-fleet phase): warm_over_cold is the fraction of a
        # cold fleet bring-up a WARM replacement still pays — a COST,
        # so the gap/numerics mirror rule: latest above (1 + tol) x
        # the best (lowest) prior-revision ratio AND past an absolute
        # floor fails. A warm pass that re-compiled anything it
        # should have disk-loaded (warm_skipped_all_compiles false)
        # fails outright — that is the persistent store silently not
        # working, not a slow box.
        cur_reint = _reint_ratio(latest)
        if cur_reint is not None:
            reint = latest.get("reintegration") or {}
            rout = {"warm_over_cold": cur_reint,
                    "cold_s": reint.get("cold_s"),
                    "warm_s": reint.get("warm_s"),
                    "warm_skipped_all_compiles":
                        reint.get("warm_skipped_all_compiles"),
                    "ratio_vs_history": None, "baseline_rev": None,
                    "regressed": False}
            if reint.get("warm_skipped_all_compiles") is False:
                rout["regressed"] = True
                out["pass"] = False
            prior = [(_reint_ratio(prev), prev.get("rev"))
                     for prev in history]
            prior = [p for p in prior if p[0] is not None]
            other_rev = [p for p in prior if p[1] != latest.get("rev")]
            pool = other_rev or prior
            if pool:
                best_r, best_rev = min(pool)
                if best_r > 0:
                    rout["ratio_vs_history"] = round(
                        cur_reint / best_r, 4)
                rout["baseline_rev"] = best_rev
                if best_rev != latest.get("rev") and cur_reint > max(
                        best_r * (1.0 + tol),
                        best_r + REINTEGRATION_FLOOR_RATIO):
                    rout["regressed"] = True
                    out["pass"] = False
            out["reintegration"] = rout
        # fleet capacity regression: achieved rates are the bytes/s
        # rule again — the latest record's req/s / tok/s below
        # (1 - tol) x the best prior-revision record for the same
        # (config, process_role) fails, so a fleet role cannot quietly
        # lose capacity between revisions (the elastic scaler sizes
        # fleets from these numbers). Same-rev priors report-only,
        # same-device only, like every other check here.
        cap = latest.get("capacity")
        if isinstance(cap, dict):
            out["capacity"] = {}
            for rate_key in ("req_per_s", "tok_per_s"):
                cur_rate = cap.get(rate_key)
                rout = {"value": cur_rate, "ratio_vs_history": None,
                        "baseline_rev": None, "regressed": False}
                prior = [(prev.get("capacity", {}).get(rate_key),
                          prev.get("rev")) for prev in history
                         if isinstance(prev.get("capacity"), dict)]
                prior = [p for p in prior if p[0]]
                other_rev = [p for p in prior
                             if p[1] != latest.get("rev")]
                pool = other_rev or prior
                if pool and cur_rate:
                    best, best_rev = max(pool)
                    rout["ratio_vs_history"] = round(cur_rate / best, 4)
                    rout["baseline_rev"] = best_rev
                    if best_rev != latest.get("rev") \
                            and cur_rate / best < 1.0 - tol:
                        rout["regressed"] = True
                        out["pass"] = False
                out["capacity"][rate_key] = rout
        verdict["configs"][config] = out
        verdict["pass"] = verdict["pass"] and out["pass"]
    if only_config and not verdict["configs"]:
        verdict["pass"] = False
        verdict["error"] = f"no ledger records for config {only_config!r}"
    return verdict


def trajectory(records) -> str:
    """Human table: one line per (record, family) in ledger order,
    plus a gap line per record carrying a dispatch_gap and a sweep
    line per recorded autotune sweep."""
    lines = [f"{'config':<22} {'rev':<19} {'family':<16} "
             f"{'runs':>5} {'GB/s':>9} {'util_hbm':>9} {'util_flops':>10}"]
    for _ln, rec in records:
        ckey = _config_key(rec)
        for family, f in sorted(rec["families"].items()):
            bps = f.get("achieved_bytes_per_s")
            uh, uf = f.get("utilization_hbm"), f.get("utilization_flops")
            lines.append(
                f"{ckey:<22} {rec.get('rev', '?'):<19} "
                f"{family:<16} {f.get('runs', 0):>5} "
                f"{'-' if not bps else f'{bps / 1e9:9.3f}':>9} "
                f"{'-' if uh is None else f'{uh:9.4f}':>9} "
                f"{'-' if uf is None else f'{uf:10.4f}':>10}")
        gap = _gap_ms(rec)
        if gap is not None:
            lines.append(f"{ckey:<22} {rec.get('rev', '?'):<19} "
                         f"{'(dispatch gap)':<16} "
                         f"{gap:9.4f} ms/step")
        gc = rec.get("graph_cache")
        if isinstance(gc, dict):
            lines.append(
                f"{ckey:<22} {rec.get('rev', '?'):<19} "
                f"{'(graph cache)':<16} "
                + " ".join(f"{k}={gc.get(k, 0)}"
                           for k in ("hit", "miss", "bypass")))
        nr = _numerics_ratio(rec)
        if nr is not None:
            gnorm = (rec.get("numerics") or {}).get("grad_norm")
            lines.append(
                f"{ckey:<22} {rec.get('rev', '?'):<19} "
                f"{'(numerics)':<16} "
                f"overhead=x{nr:.4f}"
                + (f" grad_norm={gnorm:.4g}" if gnorm is not None
                   else ""))
        rr = _reint_ratio(rec)
        if rr is not None:
            reint = rec.get("reintegration") or {}
            lines.append(
                f"{ckey:<22} {rec.get('rev', '?'):<19} "
                f"{'(reintegration)':<16} "
                f"warm/cold=x{rr:.4f} "
                f"cold={reint.get('cold_s', '-')}s "
                f"warm={reint.get('warm_s', '-')}s "
                f"all_disk_hits={reint.get('warm_skipped_all_compiles')}")
        cap = rec.get("capacity")
        if isinstance(cap, dict):
            req, tok = cap.get("req_per_s"), cap.get("tok_per_s")
            lines.append(
                f"{ckey:<22} {rec.get('rev', '?'):<19} "
                f"{'(capacity)':<16} "
                f"req/s={'-' if req is None else f'{req:.3f}'} "
                f"tok/s={'-' if tok is None else f'{tok:.1f}'} "
                f"window={cap.get('window_s', '-')}s")
        for sw in rec.get("autotune_sweeps", ()):
            lines.append(
                f"{ckey:<22} {rec.get('rev', '?'):<19} (autotune "
                f"{'|'.join(str(p) for p in sw.get('key', []))}: "
                f"winner={tuple(sw.get('winner', ()))} "
                f"validated={sw.get('window_validated')} "
                f"persisted={sw.get('persisted')})")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="perf-ledger trajectory / per-family regression "
                    "attribution")
    ap.add_argument("--ledger", default=default_ledger_path())
    ap.add_argument("--check", action="store_true",
                    help="diff the latest record per config against "
                         "ledger history; exit 1 on an attributed "
                         "regression or a disappeared family")
    ap.add_argument("--config", default=None,
                    help="restrict --check to one bench config")
    ap.add_argument("--tol", type=float, default=0.2,
                    help="--check fails a family below (1 - tol) x its "
                         "best prior-revision rate")
    args = ap.parse_args(argv)

    if not os.path.exists(args.ledger):
        print(json.dumps({"pass": False,
                          "error": f"no ledger at {args.ledger} — run "
                                   "bench.py (without --no-ledger) "
                                   "first"}))
        return 2
    records, bad = load(args.ledger)
    if not records:
        print(json.dumps({"pass": False, "malformed_lines": bad,
                          "error": "ledger holds no usable records"}))
        return 2
    if args.check:
        verdict = check(records, args.tol, args.config)
        if bad:
            verdict["malformed_lines"] = bad
        print(json.dumps(verdict, sort_keys=True))
        return 0 if verdict["pass"] else 1
    print(trajectory(records))
    return 0


if __name__ == "__main__":
    sys.exit(main())
