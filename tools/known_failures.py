#!/usr/bin/env python
"""Machine-checkable "no NEW tier-1 failures".

A test box may have ENVIRONMENT failures that every tier-1 run reports
(tools/known_failures.json lists them; on the installed jax 0.9.0 the
list is empty) and timing-sensitive tests that flake. "Tests no worse
than the seed" used to mean eyeballing the failure list against a
prose note; this tool makes it a gate:

    set -o pipefail
    ... python -m pytest tests/ -q ... | tee /tmp/_t1.log
    python tools/known_failures.py /tmp/_t1.log

Exit 0 when every FAILED/ERROR nodeid in the log is in the manifest
(known environment failures may also be ABSENT — a fix is progress,
reported as such); exit 1 listing each NEW failure otherwise. Entries
under "flaky" (timing-sensitive tests that measure real wall clocks
on a shared box) are reported when they fail but never fatal — rerun
them standalone before treating one as a regression.

`--staleness` audits the manifest itself: entries whose nodeid no
longer exists in the tree (file deleted, test renamed) or that did
not fail this run are flagged so the manifest tracks reality instead
of accreting dead entries. The staleness report is informational —
it never changes the exit code — and a one-line summary rides every
default run so drift is visible without asking for it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Dict, List, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_MANIFEST = os.path.join(_HERE, "known_failures.json")


def load_manifest(path: Optional[str] = None) -> Dict:
    with open(path or DEFAULT_MANIFEST, encoding="utf-8") as f:
        m = json.load(f)
    for key in ("failures", "flaky"):
        if not isinstance(m.get(key), list):
            raise ValueError(
                f"manifest {path or DEFAULT_MANIFEST}: missing or "
                f"non-list {key!r} key")
    return m


def parse_failures(text: str) -> List[str]:
    """Failed/errored nodeids from a pytest -q log, deduped in first-
    seen order (the summary can repeat a nodeid, e.g. a test that both
    failed and errored at teardown)."""
    seen, out = set(), []
    for line in text.splitlines():
        if not line.startswith(("FAILED ", "ERROR ")):
            continue
        parts = line.split()
        if len(parts) < 2:
            continue
        nodeid = parts[1]
        if nodeid not in seen:
            seen.add(nodeid)
            out.append(nodeid)
    return out


@dataclasses.dataclass
class Report:
    new: List[str]                  # failures NOT in the manifest
    known_seen: List[str]           # manifest failures that occurred
    known_missing: List[str]        # manifest failures that did NOT
    flaky_seen: List[str]           # flaky tests that failed this run

    @property
    def ok(self) -> bool:
        return not self.new


def check_log(log_path: str, manifest_path: Optional[str] = None
              ) -> Report:
    m = load_manifest(manifest_path)
    with open(log_path, encoding="utf-8", errors="replace") as f:
        failed = parse_failures(f.read())
    known = set(m["failures"])
    flaky = set(m["flaky"])
    return Report(
        new=[n for n in failed if n not in known and n not in flaky],
        known_seen=[n for n in failed if n in known],
        known_missing=sorted(known - set(failed)),
        flaky_seen=[n for n in failed if n in flaky],
    )


def classify_staleness(manifest: Dict, failed: List[str],
                       root: Optional[str] = None) -> Dict[str, List[str]]:
    """Audit manifest entries (failures + flaky) against the tree and
    this run's failure set. Buckets:

    - "file_missing": the test file no longer exists — the entry is
      definitely stale, delete it.
    - "test_missing": the file exists but defines no matching test
      function — renamed or removed, delete or update the entry.
    - "absent_this_run": the test still exists but did not fail this
      run — it may pass now (fixed? environment changed?) or simply
      have been deselected; candidate for manifest removal after a
      full-tree run confirms it.
    """
    root = root or os.path.dirname(_HERE)
    failed_set = set(failed)
    out: Dict[str, List[str]] = {
        "file_missing": [], "test_missing": [], "absent_this_run": []}
    src_cache: Dict[str, Optional[str]] = {}
    for nodeid in sorted(set(manifest["failures"]) | set(manifest["flaky"])):
        path = nodeid.split("::", 1)[0]
        fpath = os.path.join(root, path)
        if fpath not in src_cache:
            try:
                with open(fpath, encoding="utf-8") as f:
                    src_cache[fpath] = f.read()
            except OSError:
                src_cache[fpath] = None
        src = src_cache[fpath]
        if src is None:
            out["file_missing"].append(nodeid)
            continue
        # last :: component is the test function; strip the
        # parametrization id ("test_x[cpu-4]" -> "test_x")
        name = nodeid.rsplit("::", 1)[-1].split("[", 1)[0]
        if f"def {name}" not in src:
            out["test_missing"].append(nodeid)
        elif nodeid not in failed_set:
            out["absent_this_run"].append(nodeid)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="check a tier-1 pytest log against the known-"
                    "environment-failure manifest")
    ap.add_argument("log", help="pytest output log (tee of tier-1)")
    ap.add_argument("--manifest", default=None,
                    help=f"manifest path (default {DEFAULT_MANIFEST})")
    ap.add_argument("--staleness", action="store_true",
                    help="print the detailed manifest-staleness audit "
                         "(entries whose nodeid no longer exists or "
                         "that did not fail this run); never fatal")
    args = ap.parse_args(argv)
    r = check_log(args.log, args.manifest)
    print(f"known environment failures seen: {len(r.known_seen)} of "
          f"{len(r.known_seen) + len(r.known_missing)}")
    stale = classify_staleness(
        load_manifest(args.manifest),
        r.new + r.known_seen + r.flaky_seen)
    n_dead = len(stale["file_missing"]) + len(stale["test_missing"])
    print(f"manifest staleness: {n_dead} dead entries, "
          f"{len(stale['absent_this_run'])} absent this run"
          + ("" if args.staleness or not n_dead
             else " (--staleness for details)"))
    if args.staleness:
        for bucket, label in (
                ("file_missing", "test file gone — delete the entry"),
                ("test_missing", "test renamed/removed — update"),
                ("absent_this_run",
                 "did not fail this run (fixed, or deselected)")):
            for n in stale[bucket]:
                print(f"  ? {n}  [{label}]")
    if r.known_missing:
        print("known failures ABSENT this run (fixed? environment "
              "changed? update the manifest):")
        for n in r.known_missing:
            print(f"  - {n}")
    if r.flaky_seen:
        print("flaky (timing-sensitive) failures — rerun standalone "
              "before calling them regressions:")
        for n in r.flaky_seen:
            print(f"  ~ {n}")
    if r.new:
        print(f"NEW failures ({len(r.new)}) — these are regressions:")
        for n in r.new:
            print(f"  ! {n}")
        return 1
    print("no new failures")
    return 0


if __name__ == "__main__":
    sys.exit(main())
