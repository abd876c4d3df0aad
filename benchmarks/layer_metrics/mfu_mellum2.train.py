"""Model FLOP/s utilization of a Mellum2 cell: the operations the forward
and backward passes require per token (`harness/mellum2_flops.py`: the 8
chosen of every layer's 64 experts, attention by window, the whole head;
recomputed ones not counted) times the tokens of a step, over the
device's own step cadence in the traced window and the bf16 peak of all
the cell's chips: the step is one program over them."""
from harness import mellum2_flops, peaks


def read(run):
    peak = peaks.peaks(run.device["kind"])
    period = run.trace_summary.module_period_s(r"jit_step")
    if peak is None or period is None:
        return None
    per_token = mellum2_flops.train_flops_per_token(run.cfg, run.mix["seq"])
    tokens = run.window["tokens_per_step"]
    return 100.0 * per_token * tokens / period / (
        run.window["chips"] * peak["bf16_flops_per_s"])
