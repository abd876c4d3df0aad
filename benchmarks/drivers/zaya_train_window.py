"""The training window of the ZAYA1 cells: `drivers/train_window.py`'s
window, operations, first steps and comparison, on a `TrainStep` that
holds `paddle_tpu.models.ZayaForCausalLM` (harness/zaya_program.py).
What differs from the Laguna cells' driver (whose `Counted`, `compare`
and `choices_differ` are imported, not copied): the step's aux is, a
layer, the counts of tokens on held experts, the mean chosen probability
and each token's choice; the notes carry `moe.top1_weight_mean` beside
the load figures and, of the first batch, the share of tokens whose
choice differs from the float32 reference's (one choice a token decides
the token's whole routed output: the share is read, not bounded)."""
from __future__ import annotations

import gc
import math

import numpy as np

from drivers.laguna_train_window import Counted, choices_differ, compare
from drivers.train_window import batch, leaf_norms, window, worst_leaves
from harness import runlib, zaya_program
from harness.runlib import clock


def build_step(cfg: dict, seed: int, ref):
    """The program's training step with the seed's weights in it."""
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.optimizer import AdamW

    tr = cfg["training"]
    model = zaya_program.build_model(
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
        return crit(logits, labels), (m.expert_counts, m.router_top_weight,
                                     m.expert_choice)

    step = TrainStep(model, opt, loss_fn, has_aux=True)
    if list(step._pnames) != [n for n, _s, _i in ref.param_specs(cfg)]:
        raise RuntimeError("the program orders its parameters otherwise "
                           "than the reference")
    return Counted(step)


def first_steps(step, cfg, mix, seed, ref, n_steps):
    """`train_window.first_steps` with the reference's own
    `change_norms` (its seeded weights are not all plain normal draws)."""
    losses, grads = [], None
    for k in range(n_steps):
        losses.append(float(step(*batch(cfg, mix, seed, k)).numpy()))
        if k == 0:
            grads = leaf_norms(step, cfg["training"]["optimizer"]["beta1"])
    return {"losses": losses, "grad_norms": grads,
            "change_norms": ref.change_norms(
                step.params, ref.param_specs(cfg), seed)}


def reference_steps(cfg, mix, seed, ref, n_steps, rnd=None, parts=()):
    """`train_window.reference_steps`, and of the first step the counts
    of tokens that chose each held expert and the mean chosen
    probability. `parts`: what a deliberately broken copy leaves out."""
    trainer = ref.Trainer(
        cfg, seed, cfg["training"]["optimizer"], n_steps,
        rnd=rnd or ref.exact, row_block=ref.ROW_BLOCK, parts=parts)
    losses, grads = [], None
    for k in range(n_steps):
        loss, norms = trainer.step(*batch(cfg, mix, seed, k))
        losses.append(loss)
        if k == 0:
            grads = norms
    return {"losses": losses, "grad_norms": grads,
            "change_norms": trainer.change_norms(),
            "held_counts": trainer.held_counts,
            "top_weight_mean": trainer.top_weight_mean,
            "choices": trainer.choices}


def run(ctx) -> dict:
    """As `laguna_train_window.run`, around this `build_step` and its
    aux."""
    from paddle_tpu.nn import observe_expert_load
    from paddle_tpu.observability import perf
    cfg, mix, cell, ref = ctx.cfg, ctx.mix, ctx.cell, ctx.ref
    n_check = ref.CHECK_STEPS
    step = build_step(cfg, ctx.seed, ref)
    prog = first_steps(step, cfg, mix, ctx.seed, ref, n_check)
    # one more step, unread: the window's own steady cadence is warm
    float(step(*batch(cfg, mix, ctx.seed, n_check)).numpy())
    first_counts, first_top, first_choice = (
        np.asarray(a) for a in step.counts[0])
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
    loads = [observe_expert_load(np.asarray(c), assignments, np.asarray(t))
             for c, t, _choice in step.counts[:len(steps)]]
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
             "moe": {**moe,
                     **choices_differ(first_counts,
                                      reference["held_counts"]),
                     "choices_differ_share_by_layer": np.mean(
                         first_choice != reference["choices"],
                         axis=1).tolist(),
                     "first_batch_top1_weight_mean": {
                         "program": first_top.tolist(),
                         "reference":
                             reference["top_weight_mean"].tolist()}},
             "reference_s": clock() - t_ref,
             "paths": {k: record.get(k) for k in
                       ("moe", "cca", "attention", "flash_causal",
                        "flash_operands", "head_loss")}, **seen}
    correct = runlib.judge(compared) and not any(seen.values())
    return {"correct": correct, "attempted": len(steps), "failed": failed,
            "e2e": e2e, "peak": peak, "compared": compared, "notes": notes}
