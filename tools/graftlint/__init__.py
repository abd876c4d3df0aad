"""graftlint — framework-wide static analysis encoding the repo's
TPU invariants.

Every serious regression this repo shipped was an *invariant*
violation, not a logic typo: donated buffers that outlived their call
("Array has been deleted"), host round-trips on hot dispatch paths,
mid-serving recompiles from signature drift, observability names that
silently fell out of the documented set. GSPMD / FusionStitching apply
program analysis below the framework; graftlint applies the same
discipline to the framework's own source, so those failure classes are
machine-checked before they ship.

Usage:
    python -m tools.graftlint [paths...]         # human output
    python -m tools.graftlint --json             # machine output
    python -m tools.graftlint --update-baseline  # regenerate baseline
    python -m tools.graftlint --list-rules       # registry + docs

Rule families: donation (donate-return-alias, donate-external-buffer),
purity (host-sync-in-trace, host-sync), recompile (unstable-cache-key,
unhashable-static-arg), obs (metric-naming, span-naming,
fault-point-naming, stats-key-naming). Suppress one line with
``# graftlint: disable=<rule>``; grandfathered findings live in
``tools/graftlint/baseline.json`` (new findings always fail).

graftlint is pure stdlib — it never imports jax or paddle_tpu, so it
runs instantly anywhere (tier-1 wires it through
tests/test_graftlint.py).
"""
from .core import (                                  # noqa: F401
    Baseline, Finding, Module, Project, Report, analyze_module,
    analyze_source, build_baseline, default_baseline_path,
    iter_py_files, register, repo_root, rules, run_paths,
    write_baseline,
)
