"""The flash forward and backward kernels' least possible time (larger of
operations over peak and bytes over bandwidth) over their measured time
in the traced window."""
from harness import peaks


def read(run):
    flash = run.spec.module("kernel_costs", "flash")
    peak = peaks.peaks(run.device["kind"])
    if peak is None:
        return None
    cfg, mix = run.cfg, run.mix
    least = measured = 0.0
    for name, seconds in run.trace_summary.op_self_times():
        kind = flash.classify(name)
        if kind is not None:
            least += peaks.least_seconds(*flash.cost(
                kind, mix["batch"], mix["seq"], cfg["num_heads"],
                cfg["head_dim"]), peak)
            measured += seconds
    return 100.0 * least / measured if measured else None
