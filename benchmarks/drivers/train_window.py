"""The training window: `paddle_tpu.jit.TrainStep.__call__` on a new
seeded batch every step, the loss read every step.

An operation is one optimizer step that began and ended inside the
window; it has failed if it raised or its loss is not finite. The window
opens on an idle device and closes at the end of the step that is in
flight when `--seconds` have passed: every step counts and all the time
counts, so the rate is not quantised to whole steps.

Set-up builds ONE TrainStep, gives it the seed's weights, drives it
through its first `check_steps` steps (each on its own batch, through
the same call and feed as the window's) and hands that same object to
the window. After the window the plain reference follows those first
steps from the same seed, and `correct` compares loss, first gradient
and the parameters' change.
"""
from __future__ import annotations

import gc
import math
import statistics

import numpy as np

from harness import gpt_program, runlib, weights
from harness.runlib import annotate, clock


def build_step(cfg: dict, seed: int, ref):
    """The program's training step with the seed's weights in it."""
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.optimizer import AdamW

    tr = cfg["training"]
    model = gpt_program.build_model(
        cfg, seed, ref, "float32",
        use_flash_attention=tr["flash_attention"],
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


def batch(cfg: dict, mix: dict, seed: int, k: int):
    """Step k's token rows: [batch, seq + 1] drawn from (seed, k), split
    into inputs and next-token labels. Every row differs."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, k])
    toks = rng.integers(0, cfg["real_vocab_size"],
                        (mix["batch"], mix["seq"] + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def leaf_norms(step, beta1: float):
    """Norm of every leaf's first gradient as the optimizer got it: its
    first moment after one step is (1 - beta1) * gradient."""
    import jax
    import jax.numpy as jnp
    ms = [st["moment1"] for st in step.opt_states]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])(ms)
    return [float(n) / (1.0 - beta1) for n in norms]


def first_steps(step, cfg, mix, seed, ref, n_steps):
    """The program's side of the comparison: the first steps' losses,
    the first gradient's norms and the parameters' change."""
    losses, grads = [], None
    for k in range(n_steps):
        ids, labels = batch(cfg, mix, seed, k)
        losses.append(float(step(ids, labels).numpy()))
        if k == 0:
            grads = leaf_norms(step, cfg["training"]["optimizer"]["beta1"])
    return {"losses": losses, "grad_norms": grads,
            "change_norms": weights.change_norms(
                step.params, ref.param_specs(cfg), seed)}


def reference_steps(cfg, mix, seed, ref, n_steps, rnd=None):
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
            "change_norms": trainer.change_norms()}


def leaf_gaps(prog, ref):
    """For every leaf, the gap between the program's and the reference's
    norm (not the norm of their difference), against the reference's
    norm of that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero)."""
    floor = statistics.median(ref)
    return [abs(p - r) / max(r, floor) for p, r in zip(prog, ref)]


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """Each step's loss; the first gradient by its worst leaf; the
    parameters' change by its MEDIAN leaf. The change's worst leaf is
    every layer's `qkv_proj.bias`: the key third of that bias has a
    gradient that is zero in exact arithmetic (softmax does not see a
    shift of all keys), so Adam normalises rounding noise into steps of
    full size there, and how much noise there is differs between any two
    arithmetics (PERF.md, section 2). It is reported beside the number
    that is compared, not compared."""
    out = {}
    for k, (p, r) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss_step{k}"] = {"value": abs(p - r) / abs(r),
                                "limit": limits["loss"]}
    out["grad_norm_worst_leaf"] = {
        "value": max(leaf_gaps(prog["grad_norms"], ref["grad_norms"])),
        "limit": limits["grad_norm_worst_leaf"]}
    out["change_norm_median_leaf"] = {
        "value": statistics.median(
            leaf_gaps(prog["change_norms"], ref["change_norms"])),
        "limit": limits["change_norm_median_leaf"]}
    return out


def worst_leaves(prog: dict, ref: dict, names, k=3) -> dict:
    """For the notes: the k widest gaps of each kind, with their leaves."""
    out = {}
    for key in ("grad_norms", "change_norms"):
        gaps = sorted(zip(leaf_gaps(prog[key], ref[key]), names),
                      reverse=True)[:k]
        out[key] = [[n, g] for g, n in gaps]
    return out


def window(step, cfg, mix, seed, seconds, first_k, tracer):
    """Drive `step` for at least `seconds`. Step k+1 is dispatched before
    step k's loss is read, so reading the loss every step does not idle
    the device. Returns the steps' (begin, end, loss) and the span."""
    steps = []

    def dispatch(k):
        with annotate("harness.train.next_batch"):
            ids, labels = batch(cfg, mix, seed, k)
        with annotate("harness.train.step"):
            return clock(), step(ids, labels)

    t0 = clock()
    k = first_k
    flying = dispatch(k)
    while True:
        nxt = None
        if clock() - t0 < seconds:
            k += 1
            nxt = dispatch(k)
        with annotate("harness.train.read_loss"):
            try:
                loss = float(flying[1].numpy())
            except Exception as e:      # a step that raised has failed
                print(f"step raised: {type(e).__name__}: {e}", flush=True)
                loss = math.nan
        t_end = clock()
        steps.append((flying[0], t_end, loss))
        tracer.poll(t_end - t0)
        if nxt is None:
            break
        flying = nxt
    tracer.finish()
    return steps, t0, steps[-1][1]


def run(ctx) -> dict:
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
             "reference_losses": reference["losses"], **seen}
    correct = runlib.judge(compared) and not any(seen.values())
    return {"correct": correct, "attempted": len(steps), "failed": failed,
            "e2e": e2e, "peak": peak, "compared": compared, "notes": notes}
