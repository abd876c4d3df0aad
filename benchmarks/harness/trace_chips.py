"""A traced run's device planes one by one: `trace_scopes.ScopedTrace`
reads chip 0's operations, which is right for a cell on one chip and for
a time every chip of a mesh spends alike; a reader that wants the chips'
mean (the exchange: a chip waits for the slowest) takes each plane
through the same reduction here."""
from __future__ import annotations

import bisect

from harness.trace_reduce import OPS_LINE, short_name, union_seconds
from harness.trace_scopes import ScopedTrace, XSpace

EXCHANGE_SCOPES = ("exchange_out", "exchange_back")
REDUCE_SCATTER = "all-reduce-scatter"     # in a fused one's `hlo_category`


def of(run):
    """[ScopedTrace] of the traced run, one a device plane, made once for
    all its readers; [] where the trace holds no TPU plane."""
    if not hasattr(run, "scoped_chips"):
        run.scoped_chips = []
        if run.trace_summary.device_ops:
            space = XSpace.from_file(run.tracer.xplane())
            for plane in space.device_planes():
                chip = ScopedTrace(space, run.trace_summary)
                chip.plane = plane
                run.scoped_chips.append(chip)
    return run.scoped_chips


def in_exchange(component: str) -> bool:
    """An operation under the way out or the way back of an expert
    layer's exchange (`ops/moe_ops.py:_exchanged`), in any phase: the
    backward's collectives keep the forward's scopes."""
    return any(e in EXCHANGE_SCOPES for e in component.split("/"))


def exchange_events(chip: ScopedTrace, pattern: str = r"jit_step"):
    """[(metadata id, start, end)] of the exchange's operations on this
    chip inside the program's whole runs, and how many runs those are.
    The all-gathers (the way out; the cotangent's rows in the backward
    pass) carry the exchange's scopes. The large reduce-scatters (the
    partial sums' way back; the rows' gradients) do not: the TPU compiler
    makes each an `all-reduce-scatter fusion` that keeps no `op_name`
    (seen in the compiled step and on the chip, PR 49), so such a fusion
    is taken for the exchange's where the scoped operation that ran
    before it on the chip lies under an expert layer's `moe` (its
    operand is the way back's `moe_sum_rows`; the head's one such fusion
    follows `lm_head`'s operations and is not counted)."""
    runs = chip.runs(pattern)
    if not runs or chip.plane is None:
        return [], 0
    los = [lo for lo, _hi in runs]
    found, before = [], ""
    for mid, s, e in sorted(chip.plane.line(OPS_LINE), key=lambda ev: ev[1]):
        component = chip.scope(mid)[1]
        if component:
            before, mine = component, in_exchange(component)
        else:
            category = chip.plane.event_stats.get(mid, {}).get(
                "hlo_category", "")
            mine = REDUCE_SCATTER in category and "moe" in before.split("/")
        # counted to the whole run it starts in, as `by_scope` counts
        i = bisect.bisect_right(los, s) - 1
        if mine and i >= 0 and s <= runs[i][1]:
            found.append((mid, s, e))
    return found, len(runs)


def exchange_ms(chip: ScopedTrace, pattern: str = r"jit_step"):
    """Device self time a step of `exchange_events`, or None."""
    events, runs = exchange_events(chip, pattern)
    if not events:
        return None
    own = {(mid, s): t for mid, s, t in chip.ops()}
    return 1e3 * sum(own.get((mid, s), e - s) for mid, s, e in events) / runs


def in_flight_s(chip: ScopedTrace, pattern: str = r"jit_step"):
    """Seconds a step during which an exchange's transfer may be under
    way on this chip: the union of `exchange_events`, an asynchronous
    collective counted from its `-start`'s beginning to its `-done`'s end
    (other work may run between the two; the link is busy for some of
    it). None without a whole run or an exchange."""
    events, runs = exchange_events(chip, pattern)
    spans, open_starts = [], {}
    for mid, s, e in events:
        stem = short_name(chip.plane.event_names.get(mid, "")).split(".")[0]
        if stem.endswith("-start"):
            open_starts.setdefault(stem[:-len("-start")], []).append(s)
            spans.append((s, e))
        elif stem.endswith("-done") and open_starts.get(stem[:-len("-done")]):
            spans.append((open_starts[stem[:-len("-done")]].pop(0), e))
        else:
            spans.append((s, e))
    return union_seconds(spans) / runs if spans else None
