"""The grouped-matmul kernels' least possible time (the larger of
operations over the peak rate and bytes over the bandwidth,
`kernel_costs/gmm.py`, for the rows the window's steps really routed to
held experts: the mean of the program's counter) over their measured
self time in the traced window."""
from harness import peaks, trace_scopes


def read(run):
    gmm = run.spec.module("kernel_costs", "gmm")
    peak = peaks.peaks(run.device["kind"])
    scoped = trace_scopes.of(run)
    held = (run.window or {}).get("moe", {}).get("moe.assignments_held")
    if peak is None or not scoped or held is None:
        return None
    cfg = run.cfg
    rows = held * run.window["tokens_per_step"] * cfg["num_experts_per_tok"]
    least = measured = 0.0
    for mid, _start, seconds in scoped.ops():
        kind = gmm.classify(scoped.scope(mid)[1])
        if kind is not None:
            shapes = gmm.variants(kind, rows, cfg["num_experts"],
                                  cfg["hidden_size"],
                                  cfg["moe_intermediate_size"])
            least += sum(peaks.least_seconds(*s, peak)
                         for s in shapes) / len(shapes)
            measured += seconds
    return 100.0 * least / measured if measured else None
