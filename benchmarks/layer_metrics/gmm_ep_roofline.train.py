"""`gmm_roofline.train` for a cell whose experts lie over its chips: the
grouped-matmul kernels' least possible time on chip 0 (`kernel_costs/
gmm.py`, for the rows the window's steps really routed to chip 0's
experts, the mean of the program's counter, and the experts chip 0
holds) over their measured self time on chip 0 in the traced window.
(`gmm_roofline.train` would count every chip's rows and experts against
one chip's time.)"""
from harness import peaks, trace_scopes


def read(run):
    gmm = run.spec.module("kernel_costs", "gmm")
    peak = peaks.peaks(run.device["kind"])
    scoped = trace_scopes.of(run)
    share = (run.window or {}).get("moe", {}).get("moe.assignments_chip0")
    if peak is None or not scoped or share is None:
        return None
    cfg = run.cfg
    rows = share * run.window["tokens_per_step"] * cfg["num_experts_per_tok"]
    held = cfg["num_experts"] // run.window["chips"]
    least = measured = 0.0
    for mid, _start, seconds in scoped.ops():
        kind = gmm.classify(scoped.scope(mid)[1])
        if kind is not None:
            shapes = gmm.variants(kind, rows, held, cfg["hidden_size"],
                                  cfg["moe_intermediate_size"])
            least += sum(peaks.least_seconds(*s, peak)
                         for s in shapes) / len(shapes)
            measured += seconds
    return 100.0 * least / measured if measured else None
