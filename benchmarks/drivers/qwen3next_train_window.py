"""The training window of the Qwen3-Next cells: `drivers/train_window.py`'s
window, operations, first steps and comparison, on a `TrainStep` that
holds `paddle_tpu.models.Qwen3NextForCausalLM`
(harness/qwen3next_program.py). What differs from the Laguna cells'
driver (whose `Counted`, `compare` and `choices_differ` are imported,
not copied, and the ZAYA1 cells', whose `first_steps` measures the
change from the reference's own seeded draws): the reference takes
`parts` (what a deliberately broken copy leaves out), the comparison has
one number more (`rule_gap`: the gated delta rule alone against the
recurrence, which is what holds the rule to the float32 the
configuration states), and the notes name the Gated DeltaNet's path and
the held share at the window's ends."""
from __future__ import annotations

import functools
import gc
import math

import numpy as np

from drivers import laguna_train_window
from drivers.laguna_train_window import Counted, choices_differ
from drivers.train_window import batch, window, worst_leaves
from drivers.zaya_train_window import first_steps  # noqa: F401
from harness import qwen3next_program, runlib
from harness.runlib import clock


def build_step(cfg: dict, seed: int, ref):
    """The program's training step with the seed's weights in it."""
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.optimizer import AdamW

    tr = cfg["training"]
    model = qwen3next_program.build_model(
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
        with amp.auto_cast(enable=a["level"] != "O0", level=a["level"],
                           dtype=a["dtype"]):
            logits = m(ids)
        return crit(logits, labels), m.expert_counts

    step = TrainStep(model, opt, loss_fn, has_aux=True)
    if list(step._pnames) != [n for n, _s, _i in ref.param_specs(cfg)]:
        raise RuntimeError("the program orders its parameters otherwise "
                           "than the reference")
    return Counted(step)


RULE_DRAW = 0x6472     # folded into the seed's key for `rule_gaps`' operands
RULE_OUTPUTS = ("o", "dq", "dk", "dv", "dg", "dbeta")


def rule_gaps(cfg, mix, seed, ref, parts=None) -> dict:
    """The gated delta rule alone, at the cell's sizes, on operands drawn
    from the seed as a layer would hand them over (q of length 1 /
    sqrt(d), k of length 1, g from the configuration's draws of `A_log`
    and `dt_bias`, beta a sigmoid): the program's
    `kernels/pallas/gated_delta.gated_delta_rule`, the function the step
    runs, against the reference's recurrence token by token; o and the
    gradients to q, k, v, g and beta for a drawn cotangent, each by the
    norm of its difference over the reference's norm (`RULE_OUTPUTS`);
    `compare` takes the worst of the six. The step's other numbers are norms of leaves, and rounding that
    is as often up as down hardly moves a norm: the rule in bfloat16
    reads inside the program's own range on all of them (PERF.md,
    section 2), and 2.4e-3 here, where the program reads 1e-5.
    With `parts`, the reference's broken copy stands in the program's
    place (`tools/limits_qwen3next.py`'s control)."""
    import jax
    import jax.numpy as jnp
    from harness import weights
    _hk, heads, d = ref.linear_heads(cfg)
    rows = mix["batch"] * mix["seq"] * heads
    draw = cfg["seeded_draws"]

    @jax.jit
    def operands(key):
        ks = jax.random.split(key, 8)

        def normal(k, *last):   # [rows, ...] as [batch, seq, heads, ...]
            x = jax.random.normal(k, (rows,) + last, jnp.float32)
            return x.reshape((mix["batch"], mix["seq"], heads) + last)

        def unit(x):
            return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                     + 1e-6)

        a_log = ref.leaf(ks[0], 0, (heads,), ("log_uniform", *draw["A"]),
                         jnp.float32)
        dt_bias = ref.leaf(ks[1], 0, (heads,), ("dt_bias", *draw["dt"]),
                           jnp.float32)
        g = -jnp.exp(a_log) * jax.nn.softplus(normal(ks[2]) + dt_bias)
        return (unit(normal(ks[3], d)) / math.sqrt(d), unit(normal(ks[4], d)),
                normal(ks[5], d), g, jax.nn.sigmoid(normal(ks[6]))), \
            normal(ks[7], d)

    def with_gradients(rule):
        @jax.jit
        def run(xs, w):
            o, back = jax.vjp(rule, *xs)
            return (o, *back(w))
        return run

    if parts is None:
        from paddle_tpu.kernels.pallas.gated_delta import gated_delta_rule
    else:
        gated_delta_rule = functools.partial(ref.recurrence, parts=parts)
    xs, w = operands(jax.random.fold_in(weights.key_of(seed), RULE_DRAW))
    got = with_gradients(gated_delta_rule)(xs, w)
    exact = with_gradients(ref.recurrence)(xs, w)
    return {name: float(jnp.linalg.norm((a - b).ravel())
                        / jnp.linalg.norm(b.ravel()))
            for name, a, b in zip(RULE_OUTPUTS, got, exact)}


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """`laguna_train_window.compare`, and `rule_gap`, the worst of
    `rule_gaps`, where the program's side has read them."""
    out = laguna_train_window.compare(prog, ref, limits)
    if "rule_gaps" in prog:
        out["rule_gap"] = {"value": max(prog["rule_gaps"].values()),
                           "limit": limits["rule_gap"]}
    return out


def reference_steps(cfg, mix, seed, ref, n_steps, rnd=None, parts=()):
    """`laguna_train_window.reference_steps` with `parts`: what a
    deliberately broken copy leaves out."""
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
            "held_counts": trainer.held_counts}


def run(ctx) -> dict:
    """As `laguna_train_window.run`, around this `build_step`."""
    from paddle_tpu.nn import observe_expert_load
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
    counts = [np.asarray(c) for c in step.counts[:len(steps)]]
    loads = [observe_expert_load(c, assignments) for c in counts]
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
    prog["rule_gaps"] = rule_gaps(cfg, mix, ctx.seed, ref)
    reference = reference_steps(cfg, mix, ctx.seed, ref, n_check)
    compared = compare(prog, reference, cell["limits"])
    names = [n for n, _s, _i in ref.param_specs(cfg)]
    held = [float(c.sum(axis=1).mean() / assignments) for c in counts]
    notes = {"window_s": span, "steps": len(steps),
             "worst_leaves": worst_leaves(prog, reference, names),
             "check_steps": n_check, "rule_gaps": prog["rule_gaps"],
             "program_losses": prog["losses"],
             "reference_losses": reference["losses"],
             "moe": {**moe, **choices_differ(
                 first_counts, reference["held_counts"]),
                 # the share trains its router towards the held experts:
                 # the held share of the window's first and last steps
                 "assignments_held_first_last": held[:1] + held[-1:]},
             "reference_s": clock() - t_ref,
             "paths": {k: record.get(k) for k in
                       ("gdn", "moe", "attention", "flash_causal",
                        "flash_kept", "rope", "head_loss")}, **seen}
    correct = runlib.judge(compared) and not any(seen.values())
    return {"correct": correct, "attempted": len(steps), "failed": failed,
            "e2e": e2e, "peak": peak, "compared": compared, "notes": notes}
