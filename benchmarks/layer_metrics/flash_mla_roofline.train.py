"""The two-part flash kernels' least possible time in a DeepSeek-V2 cell
(`kernel_costs/flash_mla.py:cost` for every call at the configuration's
`num_attention_heads` heads of `qk_nope_head_dim` beside one shared
rotary head of `qk_rope_head_dim`, values of `v_head_dim`, every key in
sight) over their measured self time in the traced window. Nothing to
read in a program without those kernels."""
from harness import peaks, trace_scopes


def read(run):
    flash = run.spec.module("kernel_costs", "flash_mla")
    peak = peaks.peaks(run.device["kind"])
    scoped = trace_scopes.of(run)
    cfg, mix = run.cfg, run.mix
    if peak is None or not scoped or "kv_lora_rank" not in cfg:
        return None
    least = measured = 0.0
    for mid, _start, seconds in scoped.ops():
        found = flash.classify(scoped.scope(mid)[1])
        if found is not None:
            least += peaks.least_seconds(*flash.cost(
                found[0], mix["batch"], mix["seq"],
                cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"]), peak)
            measured += seconds
    return 100.0 * least / measured if measured else None
