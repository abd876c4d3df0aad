"""The flash kernels' least possible time in a Qwen3-Next cell
(`kernel_costs/flash_window.py:cost` for every call at the full layers'
heads: `num_attention_heads` on `num_key_value_heads` of `head_dim` 256,
every key in sight, no window) over their measured self time in the
traced window, as `flash_cca_roofline.train` has it for ZAYA1."""
from harness import peaks, trace_scopes


def read(run):
    flash = run.spec.module("kernel_costs", "flash_window")
    peak = peaks.peaks(run.device["kind"])
    scoped = trace_scopes.of(run)
    cfg, mix = run.cfg, run.mix
    if peak is None or not scoped or "full_attention_interval" not in cfg:
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
