"""Model FLOP/s utilization of a DeepSeek-V2 cell: the operations the
forward and backward passes require per token on this chip's share
(`harness/deepseek_v2_flops.py`: held assignments at their expectation,
latent attention's causal product at 192 / 128 lanes, the head over the
slice; recomputed ones not counted) times the tokens of a step, over the
device's own step cadence in the traced window and the chip's bf16 peak,
as `mfu.train` has it for the GPT cells: the share of the whole step."""
from harness import deepseek_v2_flops, peaks


def read(run):
    peak = peaks.peaks(run.device["kind"])
    period = run.trace_summary.module_period_s(r"jit_step")
    if peak is None or period is None or "kv_lora_rank" not in run.cfg:
        return None
    per_token = deepseek_v2_flops.train_flops_per_token(run.cfg,
                                                        run.mix["seq"])
    tokens = run.window["tokens_per_step"]
    return 100.0 * per_token * tokens / period / peak["bf16_flops_per_s"]
