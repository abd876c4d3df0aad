"""Model FLOP/s utilization: the operations the forward and backward
passes require per token (recomputed ones not counted) times the tokens
of a step, over the device's own step cadence in the traced window (the
median time from one run of the step program to the next) and the chip's
bf16 peak. The traced run's host clock is not used: tracing slows the
host."""
from harness import model_flops, peaks


def read(run):
    peak = peaks.peaks(run.device["kind"])
    period = run.trace_summary.module_period_s(r"jit_step")
    if peak is None or period is None:
        return None
    per_token = model_flops.train_flops_per_token(run.cfg, run.mix["seq"])
    tokens = run.window["tokens_per_step"]
    return 100.0 * per_token * tokens / period / peak["bf16_flops_per_s"]
