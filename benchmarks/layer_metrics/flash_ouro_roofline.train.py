"""The flash kernels' least possible time in an Ouro cell
(`kernel_costs/flash_window.py:cost` for every call at the
configuration's `num_attention_heads` on `num_key_value_heads` of
`head_dim`, every key in sight, no window) over their measured self time
in the traced window, as `flash_cca_roofline.train` has it for ZAYA1.
The kernels stand once a layer in the program, inside the `while` body
of the loop over the passes, and the trace holds one event for each time
one ran: `total_ut_steps` calls a layer and step, each costed."""
from harness import peaks, trace_scopes


def read(run):
    flash = run.spec.module("kernel_costs", "flash_window")
    peak = peaks.peaks(run.device["kind"])
    scoped = trace_scopes.of(run)
    cfg, mix = run.cfg, run.mix
    if peak is None or not scoped or "total_ut_steps" not in cfg:
        return None
    least = measured = 0.0
    for mid, _start, seconds in scoped.ops():
        found = flash.classify(scoped.scope(mid)[1])
        if found is not None:
            least += peaks.least_seconds(*flash.cost(
                found[0], mix["batch"], mix["seq"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"]), peak)
            measured += seconds
    return 100.0 * least / measured if measured else None
