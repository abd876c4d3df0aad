"""The gated delta rule's state kernels' least possible time (the larger
of operations over the peak rate and bytes over the bandwidth,
`kernel_costs/gated_delta.py`; the bytes bind: the state a chunk) over
their measured self time in the traced window."""
from harness import peaks, trace_scopes
from harness.qwen3next_flops import CHUNK


def read(run):
    rule = run.spec.module("kernel_costs", "gated_delta")
    peak = peaks.peaks(run.device["kind"])
    scoped = trace_scopes.of(run)
    cfg, mix = run.cfg, run.mix
    if peak is None or not scoped or "linear_num_value_heads" not in cfg:
        return None
    shape = (mix["batch"] * cfg["linear_num_value_heads"],
             mix["seq"] // CHUNK, CHUNK, cfg["linear_key_head_dim"],
             cfg["linear_value_head_dim"])
    least = measured = 0.0
    for mid, _start, seconds in scoped.ops():
        kind = rule.classify(scoped.scope(mid)[1])
        if kind is not None:
            least += peaks.least_seconds(*rule.cost(kind, *shape), peak)
            measured += seconds
    return 100.0 * least / measured if measured else None
