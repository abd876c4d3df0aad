"""Per-path rule configuration.

Analysis-exempt paths: operator-facing CLIs whose JOB is host I/O —
dashboards and dumps that print — are exempt from the host-sync
inventory (the warning-level round-trip burn-down rule). They are NOT
exempt from the error-level rules: a donation bug or a trace-impure
scan body in a tool is still a bug.

The exemption list is a public contract pinned by
tests/test_graftlint.py::test_exemption_list_pinned — extending it is
a reviewed decision, not a side effect.
"""
from __future__ import annotations

from typing import FrozenSet

# path (repo-root-relative, forward slashes) -> rule ids disabled there
PATH_EXEMPTIONS = {
    # operator CLIs: reading and rendering host-side is their purpose,
    # not a dispatch-path regression
    "tools/obs_top.py": frozenset({"host-sync"}),
    "tools/obs_dump.py": frozenset({"host-sync"}),
    "tools/gen_ops_parity.py": frozenset({"host-sync"}),
}


# eager-dispatch hot path: the host-clock audit (purity rule
# host-clock-in-dispatch) inventories wall-clock reads ONLY under
# these prefixes — a stray perf_counter in the per-node/fused backward
# loop, the op dispatcher, or the fused optimizer step is pure
# per-dispatch overhead (ROADMAP item 4), so every site must be
# justified into the baseline. optimizer.py joined in ISSUE 13: the
# fused step is the third dispatch in the steady-state eager train
# loop (forward ops -> one whole-graph backward -> one fused step),
# so its host costs are budgeted like the backward engine's.
DISPATCH_CLOCK_AUDIT_PATHS = (
    "paddle_tpu/autograd/",
    "paddle_tpu/ops/registry.py",
    "paddle_tpu/optimizer/optimizer.py",
)


def disabled_for(path: str) -> FrozenSet[str]:
    return PATH_EXEMPTIONS.get(path, frozenset())
