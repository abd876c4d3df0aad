"""The flash kernels' least possible time in a cell whose layers differ
(`kernel_costs/flash_window.py`: each call by its layer's query heads,
key/value heads and window, read off the call's scope path) over their
measured self time in the traced window."""
from harness import peaks, trace_scopes


def read(run):
    flash = run.spec.module("kernel_costs", "flash_window")
    peak = peaks.peaks(run.device["kind"])
    scoped = trace_scopes.of(run)
    if peak is None or not scoped:
        return None
    cfg, mix = run.cfg, run.mix
    least = measured = 0.0
    for mid, _start, seconds in scoped.ops():
        found = flash.classify(scoped.scope(mid)[1])
        if found is not None:
            kind, layer = found
            least += peaks.least_seconds(*flash.cost(
                kind, mix["batch"], mix["seq"],
                *flash.layer_shape(cfg, layer)), peak)
            measured += seconds
    return 100.0 * least / measured if measured else None
