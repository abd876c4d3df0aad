"""The training window of the Jamba cells: `drivers/train_window.py`'s
window, operations, first steps and comparison, on a `TrainStep` that
holds `paddle_tpu.models.JambaForCausalLM` (harness/jamba_program.py)
instead of the GPT. Nothing else differs: an operation is one optimizer
step that began and ended in the window, failed if it raised or its loss
is not finite, and `correct` compares what it compares there, but for
the losses where the cell sets no limit on them (`compare` below). The
notes also say which paths the step's program took
(`compile_record("train_step")`: the scan, the attention, the head)."""
from __future__ import annotations

import gc
import math

from drivers import train_window
from drivers.train_window import (batch, first_steps, reference_steps,
                                  window, worst_leaves)
from harness import jamba_program, runlib
from harness.runlib import clock


def build_step(cfg: dict, seed: int, ref):
    """The program's training step with the seed's weights in it."""
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.optimizer import AdamW

    tr = cfg["training"]
    model = jamba_program.build_model(
        cfg, seed, ref, use_flash_attention=tr["flash_attention"],
        recompute=tr["recompute_interval"] > 0,
        recompute_interval=max(tr["recompute_interval"], 1))
    model.train()
    o = tr["optimizer"]
    opt = AdamW(learning_rate=o["learning_rate"], beta1=o["beta1"],
                beta2=o["beta2"], epsilon=o["epsilon"],
                parameters=model.parameters(),
                weight_decay=o["weight_decay"],
                moment_dtype=o["moment_dtype"])
    crit = GPTPretrainingCriterion()
    a = tr["amp"]

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level=a["level"], dtype=a["dtype"]):
            logits = m(ids)
        return crit(logits, labels)

    step = TrainStep(model, opt, loss_fn)
    if list(step._pnames) != [n for n, _s, _i in ref.param_specs(cfg)]:
        raise RuntimeError("the program orders its parameters otherwise "
                           "than the reference")
    return step


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """`train_window.compare`, less the losses where the cell gives them
    no limit. At this size a step's loss is a mean over 4096 tokens of a
    65536-way softmax: the bf16 program reads 2e-6 to 8.6e-5 from the
    reference's and the fp8 control 8.5e-5 to 8.1e-4, so no limit lies
    between the two readings (PERF.md section 2). The first gradient and
    the parameters' change tell them apart by fifty times and more; the
    losses of both sides are in the run's notes."""
    out = train_window.compare(prog, ref, {"loss": math.inf, **limits})
    if "loss" not in limits:
        out = {k: v for k, v in out.items() if not k.startswith("loss_")}
    return out


def run(ctx) -> dict:
    """As `train_window.run`, line for line, around this `build_step`."""
    cfg, mix, cell, ref = ctx.cfg, ctx.mix, ctx.cell, ctx.ref
    n_check = ref.CHECK_STEPS
    step = build_step(cfg, ctx.seed, ref)
    prog = first_steps(step, cfg, mix, ctx.seed, ref, n_check)
    # one more step, unread: the window's own steady cadence is warm
    float(step(*batch(cfg, mix, ctx.seed, n_check)).numpy())

    watch = ctx.watch
    watch.arm()
    setup_s = clock() - ctx.t_process
    steps, t0, t1 = window(step, cfg, mix, ctx.seed, ctx.seconds,
                           n_check + 1, ctx.tracer)
    seen = watch.disarm()
    peak = runlib.memory_peak_bytes([getattr(step._step_fn, "fn", None)])

    tokens = mix["batch"] * mix["seq"]
    failed = sum(1 for _b, _e, loss in steps if not math.isfinite(loss))
    span = t1 - t0
    ctx.window = {"kind": "train", "steps": steps, "t0": t0, "t1": t1,
                  "tokens_per_step": tokens, "chips": cell["chips"]}
    e2e = {"train_tok_s_chip": tokens * len(steps) / span / cell["chips"],
           "setup_s": setup_s}

    # the program's state leaves before the reference comes
    from paddle_tpu.observability import perf
    record = perf.compile_record("train_step") or {}
    del step
    gc.collect()
    t_ref = clock()
    reference = reference_steps(cfg, mix, ctx.seed, ref, n_check)
    compared = compare(prog, reference, cell["limits"])
    names = [n for n, _s, _i in ref.param_specs(cfg)]
    notes = {"window_s": span, "steps": len(steps),
             "worst_leaves": worst_leaves(prog, reference, names),
             "reference_s": clock() - t_ref, "check_steps": n_check,
             "program_losses": prog["losses"],
             "reference_losses": reference["losses"],
             "paths": {k: record.get(k) for k in
                       ("ssm_scan", "attention", "head_loss")}, **seen}
    correct = runlib.judge(compared) and not any(seen.values())
    return {"correct": correct, "attempted": len(steps), "failed": failed,
            "e2e": e2e, "peak": peak, "compared": compared, "notes": notes}
