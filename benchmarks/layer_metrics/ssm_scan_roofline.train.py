"""The selective-scan kernels' least possible time (the larger of
operations over the peak rate and bytes over the bandwidth,
`kernel_costs/ssm_scan.py`; the bytes bind) over their measured self
time in the traced window."""
from harness import peaks, trace_scopes


def read(run):
    scan = run.spec.module("kernel_costs", "ssm_scan")
    peak = peaks.peaks(run.device["kind"])
    scoped = trace_scopes.of(run)
    if peak is None or not scoped:
        return None
    cfg, mix = run.cfg, run.mix
    shape = (mix["batch"], mix["seq"],
             cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"])
    least = measured = 0.0
    for mid, _start, seconds in scoped.ops():
        kind = scan.classify(scoped.scope(mid)[1])
        if kind is not None:
            least += peaks.least_seconds(*scan.cost(kind, *shape), peak)
            measured += seconds
    return 100.0 * least / measured if measured else None
