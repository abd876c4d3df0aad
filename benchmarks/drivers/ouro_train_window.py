"""The training window of the Ouro cells: `drivers/train_window.py`'s
window, operations, first steps and comparison, on a `TrainStep` that
holds `paddle_tpu.models.OuroForCausalLM` (harness/ouro_program.py)
under `OuroPretrainingCriterion`. What differs from the other cells'
drivers (whose `Counted`, `compare`, `first_steps`, `window`, `batch` and
`worst_leaves` are imported, not copied): the step's aux is the [2, T]
of the mean CE_t and the mean p_t over the step's tokens, the reference
takes `parts` (what a deliberately broken copy leaves out), and the
comparison has one number more, `pass_gap`: the worst relative
difference, over the 2 T numbers of the first step's aux, between the
program and the reference. The loss is a weighted sum over the passes
and can hide a wrong pass behind a right one; `pass_gap` cannot."""
from __future__ import annotations

import gc
import math

import numpy as np

from drivers import laguna_train_window
from drivers.laguna_train_window import Counted
from drivers.train_window import batch, window, worst_leaves
from drivers.zaya_train_window import first_steps as _first_steps
from harness import ouro_program, runlib
from harness.runlib import clock


def build_step(cfg: dict, seed: int, ref):
    """The program's training step with the seed's weights in it."""
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import OuroPretrainingCriterion
    from paddle_tpu.optimizer import AdamW

    tr = cfg["training"]
    model = ouro_program.build_model(
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
    crit = OuroPretrainingCriterion(tr["exit_entropy_beta"])
    a = tr["amp"]

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=a["level"] != "O0", level=a["level"],
                           dtype=a["dtype"]):
            outputs = m(ids)
        return crit(outputs, labels)        # (loss, aux [2, T])

    step = TrainStep(model, opt, loss_fn, has_aux=True)
    if list(step._pnames) != [n for n, _s, _i in ref.param_specs(cfg)]:
        raise RuntimeError("the program orders its parameters otherwise "
                           "than the reference")
    return Counted(step)


def first_steps(step, cfg, mix, seed, ref, n_steps):
    """`zaya_train_window.first_steps`, and the first step's aux."""
    step.counts.clear()
    out = _first_steps(step, cfg, mix, seed, ref, n_steps)
    out["aux"] = np.asarray(step.counts[0], np.float64)
    return out


def pass_gaps(prog_aux, ref_aux) -> list:
    """The relative difference of each of the 2 T numbers (the mean
    CE_t, then the mean p_t); a pass that one side lacks reads 1."""
    prog_aux, ref_aux = np.asarray(prog_aux), np.asarray(ref_aux)
    T = max(prog_aux.shape[1], ref_aux.shape[1])

    def wide(a):
        return np.pad(a, ((0, 0), (0, T - a.shape[1])))

    p, r = wide(prog_aux), wide(ref_aux)
    return (np.abs(p - r) / np.maximum(np.abs(r), 1e-30)).ravel().tolist()


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """`laguna_train_window.compare`, and `pass_gap`."""
    out = laguna_train_window.compare(prog, ref, limits)
    out["pass_gap"] = {"value": max(pass_gaps(prog["aux"], ref["aux"])),
                       "limit": limits["pass_gap"]}
    return out


def reference_steps(cfg, mix, seed, ref, n_steps, rnd=None, parts=()):
    """`train_window.reference_steps` with `parts` (what a deliberately
    broken copy leaves out), and the first step's aux."""
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
            "aux": np.asarray(trainer.aux, np.float64)}


def run(ctx) -> dict:
    """As `train_window.run`, around this `build_step` and its aux."""
    from paddle_tpu.observability import perf
    cfg, mix, cell, ref = ctx.cfg, ctx.mix, ctx.cell, ctx.ref
    n_check = ref.CHECK_STEPS
    step = build_step(cfg, ctx.seed, ref)
    prog = first_steps(step, cfg, mix, ctx.seed, ref, n_check)
    # one more step, unread: the window's own steady cadence is warm
    float(step(*batch(cfg, mix, ctx.seed, n_check)).numpy())
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
    failed = sum(1 for _b, _e, loss in steps if not math.isfinite(loss))
    span = t1 - t0
    ctx.window = {"kind": "train", "steps": steps, "t0": t0, "t1": t1,
                  "tokens_per_step": tokens, "chips": cell["chips"]}
    e2e = {"train_tok_s_chip": tokens * len(steps) / span / cell["chips"],
           "setup_s": setup_s}
    aux = [np.asarray(a, np.float64) for a in step.counts[:len(steps)]]

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
             "pass_gaps": pass_gaps(prog["aux"], reference["aux"]),
             # the mean CE_t and the mean p_t of the first checked step
             # (the program's, the reference's) and of the window's
             # first and last steps
             "exit": {"program_first": prog["aux"].tolist(),
                      "reference_first": reference["aux"].tolist(),
                      "window_first_last": [a.tolist() for a in
                                            aux[:1] + aux[-1:]]},
             "reference_s": clock() - t_ref,
             "paths": {k: record.get(k) for k in
                       ("ut_loop", "attention", "flash_causal",
                        "flash_kept", "rope", "head_loss")}, **seen}
    correct = runlib.judge(compared) and not any(seen.values())
    return {"correct": correct, "attempted": len(steps), "failed": failed,
            "e2e": e2e, "peak": peak, "compared": compared, "notes": notes}
