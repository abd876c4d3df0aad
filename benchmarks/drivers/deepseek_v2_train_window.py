"""The training window of the DeepSeek-V2 cells: `drivers/train_window.py`'s
window, operations, first steps and comparison, on a `TrainStep` that
holds `paddle_tpu.models.DeepseekV2ForCausalLM`
(harness/deepseek_v2_program.py) under `DeepseekV2PretrainingCriterion`.
What differs from the other expert cells' drivers (whose `Counted`,
`compare`, `choices_differ`, `first_steps`, `window`, `batch` and
`worst_leaves` are imported, not copied): the step's aux is (the counts
of assignments to held experts a sparse layer, the mean balance term),
the reference takes `parts` (what a deliberately broken copy gets
wrong), the comparison has one number more (`mla_gap`: latent
attention's core alone at the cell's keys against the reference's
blocks), and the notes carry the balance term and the held share at the
window's ends."""
from __future__ import annotations

import functools
import gc
import math

import numpy as np

from drivers import laguna_train_window
from drivers.laguna_train_window import Counted, choices_differ
from drivers.train_window import batch, window, worst_leaves
from drivers.zaya_train_window import first_steps  # noqa: F401
from harness import deepseek_v2_program, runlib
from harness.runlib import clock


def build_step(cfg: dict, seed: int, ref):
    """The program's training step with the seed's weights in it."""
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.deepseek_v2 import DeepseekV2PretrainingCriterion
    from paddle_tpu.optimizer import AdamW

    tr = cfg["training"]
    model = deepseek_v2_program.build_model(
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
    crit = DeepseekV2PretrainingCriterion(cfg["aux_loss_alpha"])
    a = tr["amp"]

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=a["level"] != "O0", level=a["level"],
                           dtype=a["dtype"]):
            logits = m(ids)
        loss, balance = crit(logits, labels, m.balance_terms)
        return loss, (m.expert_counts, balance)

    step = TrainStep(model, opt, loss_fn, has_aux=True)
    if list(step._pnames) != [n for n, _s, _i in ref.param_specs(cfg)]:
        raise RuntimeError("the program orders its parameters otherwise "
                           "than the reference")
    return Counted(step)


MLA_DRAW = 0x6d6c61     # folded into the seed's key for `mla_gaps`' operands
MLA_OUTPUTS = ("o", "dq", "dq_pe", "dk", "dk_pe", "dv")


def mla_gaps(cfg, mix, seed, ref, parts=None, rnd=None) -> dict:
    """Latent attention's core alone, at the cell's rows, keys and heads,
    on operands drawn from the seed at the size a layer hands them over
    (unit normal a dimension, which with the configuration's scale gives
    scores a few units wide): the program's `causal_attention(...,
    shared=)` on bfloat16 operands as amp hands them over, the function
    the step runs, against the reference's `latent_core` on the same
    (bfloat16-valued) numbers in float32; o and the gradients to q, q',
    k, k' and v for a drawn cotangent, each by the norm of its
    difference over the reference's norm (`MLA_OUTPUTS`); `compare`
    takes the worst of the six. With `parts` or `rnd`, the reference's
    broken or rounded copy stands in the program's place
    (`tools/limits_deepseek_v2.py`'s controls)."""
    import jax
    import jax.numpy as jnp
    from harness import weights
    H, dn, dr, dv, _rank = ref.head_sizes(cfg)
    r, s = mix["batch"], mix["seq"]
    scale = ref.softmax_scale(cfg)

    @jax.jit
    def operands(key):
        ks = jax.random.split(key, 6)

        def normal(k, heads, d):    # bfloat16-valued, as amp rounds them
            x = jax.random.normal(k, (r, s, heads, d), jnp.float32)
            return x.astype(jnp.bfloat16).astype(jnp.float32)

        return (normal(ks[0], H, dn), normal(ks[1], H, dr),
                normal(ks[2], H, dn), normal(ks[3], 1, dr),
                normal(ks[4], H, dv)), normal(ks[5], H, dv)

    def with_gradients(core):
        @jax.jit
        def run(xs, w):
            o, back = jax.vjp(core, *xs)
            return (o, *back(w.astype(o.dtype)))
        return run

    exact_core = functools.partial(ref.latent_core, scale=scale)
    if parts is None and rnd is None:
        from paddle_tpu.kernels.pallas import flash_attention

        def core(q, q_pe, k, k_pe, v):
            return flash_attention(q, k, v, causal=True, softmax_scale=scale,
                                   shared=(q_pe, k_pe))

        def program(xs, w):
            low = tuple(x.astype(jnp.bfloat16) for x in xs)
            return with_gradients(core)(low, w)
    else:
        program = with_gradients(functools.partial(
            ref.latent_core, scale=ref.softmax_scale(cfg, parts or ()),
            parts=tuple(parts or ()), rnd=rnd or ref.exact))
    xs, w = operands(jax.random.fold_in(weights.key_of(seed), MLA_DRAW))
    got = program(xs, w)
    want = with_gradients(exact_core)(xs, w)
    return {name: float(jnp.linalg.norm(
        (a.astype(jnp.float32) - b).ravel()) / jnp.linalg.norm(b.ravel()))
        for name, a, b in zip(MLA_OUTPUTS, got, want)}


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """`laguna_train_window.compare`, and `mla_gap`, the worst of
    `mla_gaps`, where the program's side has read them."""
    out = laguna_train_window.compare(prog, ref, limits)
    if "mla_gaps" in prog:
        out["mla_gap"] = {"value": max(prog["mla_gaps"].values()),
                          "limit": limits["mla_gap"]}
    return out


def reference_steps(cfg, mix, seed, ref, n_steps, rnd=None, parts=()):
    """`laguna_train_window.reference_steps` with `parts` (what a
    deliberately broken copy gets wrong), and every step's balance
    term."""
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
            "held_counts": trainer.held_counts, "balance": trainer.balance}


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
    first_counts = np.asarray(step.counts[0][0])
    first_balance = [float(np.asarray(b)) for _c, b in step.counts[:n_check]]
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
    counts = [np.asarray(c) for c, _b in step.counts[:len(steps)]]
    balance = [float(np.asarray(b)) for _c, b in step.counts[:len(steps)]]
    loads = [observe_expert_load(c, assignments) for c in counts]
    moe = {k: float(np.mean([load[k] for load in loads]))
           for k in loads[0]} if loads else {}
    if balance:     # the window's mean balance term, beside the loads
        moe["moe.aux_loss"] = float(np.mean(balance))
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
    prog["mla_gaps"] = mla_gaps(cfg, mix, ctx.seed, ref)
    reference = reference_steps(cfg, mix, ctx.seed, ref, n_check)
    compared = compare(prog, reference, cell["limits"])
    names = [n for n, _s, _i in ref.param_specs(cfg)]
    held = [float(c.sum(axis=1).mean() / assignments) for c in counts]
    notes = {"window_s": span, "steps": len(steps),
             "worst_leaves": worst_leaves(prog, reference, names),
             "check_steps": n_check, "mla_gaps": prog["mla_gaps"],
             "program_losses": prog["losses"],
             "reference_losses": reference["losses"],
             "moe": {**moe, **choices_differ(
                 first_counts, reference["held_counts"]),
                 # the share trains its router towards the held experts
                 # and the balance loss pulls the other way: the held
                 # share and the mean balance term (`moe.aux_loss`, 1
                 # where the load is even) of the window's first and
                 # last steps, and the balance term of the checked steps
                 # beside the reference's
                 "assignments_held_first_last": held[:1] + held[-1:],
                 "aux_loss_first_last": balance[:1] + balance[-1:],
                 "aux_loss_check_steps": first_balance,
                 "aux_loss_reference": reference["balance"]},
             "reference_s": clock() - t_ref,
             "paths": {k: record.get(k) for k in
                       ("moe", "attention", "flash_operands",
                        "flash_causal", "flash_kept", "rope",
                        "head_loss")}, **seen}
    correct = runlib.judge(compared) and not any(seen.values())
    return {"correct": correct, "attempted": len(steps), "failed": failed,
            "e2e": e2e, "peak": peak, "compared": compared, "notes": notes}
