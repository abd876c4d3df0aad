"""The training window of the Laguna cells: `drivers/train_window.py`'s
window, operations, first steps and comparison, on a `TrainStep` that
holds `paddle_tpu.models.LagunaForCausalLM` (harness/laguna_program.py).
What differs from the Jamba cells' driver: the loss function hands the
step's per-layer counts of assignments to held experts out as the
step's aux, the driver keeps every step's (device arrays of the step's
own program, read after the window in one transfer, so nothing syncs
the device beyond the loss), and the notes carry the window's load
figures (`observe_expert_load`) and, for the first batch, the share of
assignments that differ from the reference's choices."""
from __future__ import annotations

import gc
import math

import numpy as np

from drivers import train_window
from drivers.jamba_train_window import compare
from drivers.train_window import batch, first_steps, window, worst_leaves
from harness import laguna_program, runlib
from harness.runlib import clock


class Counted:
    """A `TrainStep` that keeps every call's aux; everything else is the
    step's own (what `tools/limits_train.py:reset` sets on it too)."""

    def __init__(self, step):
        object.__setattr__(self, "step", step)
        object.__setattr__(self, "counts", [])

    def __call__(self, ids, labels):
        loss = self.step(ids, labels)
        self.counts.append(self.step.aux)
        return loss

    def __getattr__(self, name):
        return getattr(self.step, name)

    def __setattr__(self, name, value):
        setattr(self.step, name, value)


def build_step(cfg: dict, seed: int, ref):
    """The program's training step with the seed's weights in it."""
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.optimizer import AdamW

    tr = cfg["training"]
    model = laguna_program.build_model(
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
        return crit(logits, labels), m.expert_counts

    step = TrainStep(model, opt, loss_fn, has_aux=True)
    if list(step._pnames) != [n for n, _s, _i in ref.param_specs(cfg)]:
        raise RuntimeError("the program orders its parameters otherwise "
                           "than the reference")
    return Counted(step)


def reference_steps(cfg, mix, seed, ref, n_steps, rnd=None):
    """`train_window.reference_steps`, and the first step's counts of
    tokens that chose each held expert."""
    trainer = ref.Trainer(
        cfg, seed, cfg["training"]["optimizer"], n_steps,
        rnd=rnd or ref.exact, row_block=ref.ROW_BLOCK)
    losses, grads = [], None
    for k in range(n_steps):
        loss, norms = trainer.step(*batch(cfg, mix, seed, k))
        losses.append(loss)
        if k == 0:
            grads = norms
    return {"losses": losses, "grad_norms": grads,
            "change_norms": trainer.change_norms(),
            "held_counts": trainer.held_counts}


def choices_differ(counts, reference_counts) -> dict:
    """The first batch's held-expert counts of the program against the
    reference's (float32 all the way): the share of the assignments to
    held experts that moved to or from an expert, a layer. A top-k
    choice flips where the k-th and the next score nearly tie and the
    arithmetic upstream differs (bf16 against float32)."""
    return {"count_shift_share_by_layer": [
        float(np.abs(got - want).sum() / max(want.sum(), 1))
        for got, want in zip(np.asarray(counts),
                             np.asarray(reference_counts))]}


def run(ctx) -> dict:
    """As `train_window.run`, around this `build_step` and its counts."""
    from paddle_tpu.models.laguna import observe_expert_load
    from paddle_tpu.observability import perf
    cfg, mix, cell, ref = ctx.cfg, ctx.mix, ctx.cell, ctx.ref
    n_check = ref.CHECK_STEPS
    step = build_step(cfg, ctx.seed, ref)
    prog = first_steps(step, cfg, mix, ctx.seed, ref, n_check)
    # one more step, unread: the window's own steady cadence is warm
    float(step(*batch(cfg, mix, ctx.seed, n_check)).numpy())
    first_counts = np.asarray(step.counts[0])
    step.counts.clear()

    watch = ctx.watch
    watch.arm()
    setup_s = clock() - ctx.t_process
    steps, t0, t1 = window(step, cfg, mix, ctx.seed, ctx.seconds,
                           n_check + 1, ctx.tracer)
    seen = watch.disarm()
    peak = runlib.memory_peak_bytes(
        [getattr(step.step._step_fn, "fn", None)])

    tokens = mix["batch"] * mix["seq"]
    assignments = tokens * cfg["num_experts_per_tok"]
    loads = [observe_expert_load(np.asarray(c), assignments)
             for c in step.counts[:len(steps)]]
    moe = {k: float(np.mean([load[k] for load in loads]))
           for k in loads[0]} if loads else {}
    failed = sum(1 for _b, _e, loss in steps if not math.isfinite(loss))
    span = t1 - t0
    ctx.window = {"kind": "train", "steps": steps, "t0": t0, "t1": t1,
                  "tokens_per_step": tokens, "chips": cell["chips"],
                  "moe": moe}
    e2e = {"train_tok_s_chip": tokens * len(steps) / span / cell["chips"],
           "setup_s": setup_s}

    # the program's state leaves before the reference comes
    record = perf.compile_record("train_step") or {}
    del step
    gc.collect()
    t_ref = clock()
    reference = reference_steps(cfg, mix, ctx.seed, ref, n_check)
    compared = compare(prog, reference, cell["limits"])
    names = [n for n, _s, _i in ref.param_specs(cfg)]
    notes = {"window_s": span, "steps": len(steps),
             "worst_leaves": worst_leaves(prog, reference, names),
             "check_steps": n_check,
             "program_losses": prog["losses"],
             "reference_losses": reference["losses"],
             "moe": {**moe, **choices_differ(
                 first_counts, reference["held_counts"])},
             "reference_s": clock() - t_ref,
             "paths": {k: record.get(k) for k in
                       ("moe", "attention", "attention_window",
                        "flash_causal", "head_loss")}, **seen}
    correct = runlib.judge(compared) and not any(seen.values())
    return {"correct": correct, "attempted": len(steps), "failed": failed,
            "e2e": e2e, "peak": peak, "compared": compared, "notes": notes}
