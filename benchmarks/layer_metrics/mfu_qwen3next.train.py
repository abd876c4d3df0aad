"""Model FLOP/s utilization of a Qwen3-Next cell: the operations the
forward and backward passes require per token on this chip's share
(`harness/qwen3next_flops.py`: held assignments at their expectation,
the full layers' causal product, the delta rule's chunked products, the
head over the slice; recomputed ones not counted) times the tokens of a
step, over the device's own step cadence in the traced window and the
chip's bf16 peak, as `mfu.train` has it for the GPT cells."""
from harness import peaks, qwen3next_flops


def read(run):
    peak = peaks.peaks(run.device["kind"])
    period = run.trace_summary.module_period_s(r"jit_step")
    if peak is None or period is None:
        return None
    per_token = qwen3next_flops.train_flops_per_token(run.cfg,
                                                      run.mix["seq"])
    tokens = run.window["tokens_per_step"]
    return 100.0 * per_token * tokens / period / peak["bf16_flops_per_s"]
