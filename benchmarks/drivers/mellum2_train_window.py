"""The training window of the Mellum2 cells: `drivers/train_window.py`'s
window, operations, first steps and comparison, on a `TrainStep` over the
configuration's mesh (`training.mesh`: the cell's four chips, one axis)
that holds `paddle_tpu.models.Mellum2ForCausalLM`
(harness/mellum2_program.py), its experts, embedding and head laid over
that axis by `shard_plans.expert_parallel_rules`, the batch's rows over
the same axis, one a chip. What differs from the other expert cells'
drivers (whose `Counted`, `compare`, `choices_differ`, `first_steps`,
`window`, `batch` and `worst_leaves` are imported, not copied): the step's aux, the counts
of assignments an expert a layer, is over ALL the experts and all four
chips' tokens; the reference is ONE model with every expert
(`harness/mellum2_reference.py`) and takes `parts` (what a deliberately
broken copy gets wrong); the comparison has one number more
(`exchange_gap`: the exchanged expert layer alone at the cell's sizes
against the reference's layer); and the notes carry the held share at the
window's ends (1 by construction), chip 0's share of the assignments and
the exchange's bytes beside the program's own note of them."""
from __future__ import annotations

import gc
import math

import numpy as np

from drivers import laguna_train_window
from drivers.laguna_train_window import Counted, choices_differ
from drivers.train_window import batch, window, worst_leaves
from drivers.zaya_train_window import first_steps  # noqa: F401
from harness import mellum2_flops, mellum2_program, runlib
from harness.runlib import clock


def build_step(cfg: dict, seed: int, ref, rows: int = None):
    """The program's training step over the mesh with the seed's weights
    in it, every array in its shards from the start. `rows`: the batch's
    rows where they may not divide over the mesh (a CPU rehearsal's two):
    such a batch is whole on every chip, attention runs alike on all and
    the exchange still splits the tokens."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.models.shard_plans import expert_parallel_rules
    from paddle_tpu.optimizer import AdamW

    tr = cfg["training"]
    mesh = mellum2_program.mesh_of(cfg)
    model = mellum2_program.build_model(
        cfg, seed, ref, mesh, use_flash_attention=tr["flash_attention"],
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

    axis, over = tr["mesh"]["expert_axis"], tr["mesh"]["batch_axis"]
    split = rows is None or rows % mesh.shape[over] == 0
    step = TrainStep(model, opt, loss_fn, has_aux=True, mesh=mesh,
                     shard_param=expert_parallel_rules(axis),
                     shard_data=P(over, None) if split else P(),
                     expert_axis=axis)
    if list(step._pnames) != [n for n, _s, _i in ref.param_specs(cfg)]:
        raise RuntimeError("the program orders its parameters otherwise "
                           "than the reference")
    return Counted(step)


EXCHANGE_DRAW = 0x657034    # folded into the seed's key for the operands
EXCHANGE_OUTPUTS = ("y", "dx", "d_gate_up", "d_down")


def exchange_gaps(cfg, mix, seed, ref, parts=None, rnd=None) -> dict:
    """The exchanged expert layer alone, at the cell's tokens and mesh:
    `ops.moe_route` and `ops.moe_experts` under the step's mesh plan and
    amp (the functions the step's layers call, the same four-way
    `shard_map`), on a layer's leaves drawn from the seed as the
    configuration's are and on rows drawn unit normal (what an RMSNorm
    hands over), against the reference's `sparse_ffn` on the same numbers
    in float32: y and the gradients to the rows and to the two stacked
    expert matrices for a drawn cotangent, each by the norm of its
    difference over the reference's norm (`EXCHANGE_OUTPUTS`); `compare`
    takes the worst of the four. With `parts` or `rnd` the reference's
    broken or rounded copy stands in the program's place
    (`tools/limits_mellum2.py`'s controls)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from harness import weights
    from paddle_tpu import amp
    from paddle_tpu.core.mesh_plan import mesh_plan
    from paddle_tpu.ops.moe_ops import moe_experts, moe_route
    tr = cfg["training"]
    mesh = mellum2_program.mesh_of(cfg)
    axis = tr["mesh"]["expert_axis"]
    rows, seq, h = mix["batch"], mix["seq"], cfg["hidden_size"]
    top_k = cfg["num_experts_per_tok"]
    specs = ref.param_specs(cfg)
    layer = [(n, s, i) for n, s, i in specs if ".layers.0.moe." in n]
    lead = NamedSharding(mesh, P(axis))
    w_gu, w_down, w_router = ref.make(
        seed, layer, jnp.float32, [lead, lead, NamedSharding(mesh, P())])
    key = jax.random.fold_in(weights.key_of(seed), EXCHANGE_DRAW)

    @jax.jit
    def operands(key):
        ks = jax.random.split(key, 2)

        def normal(k):      # bfloat16-valued, as amp rounds them
            x = jax.random.normal(k, (rows * seq, h), jnp.float32)
            x = x.astype(jnp.bfloat16).astype(jnp.float32)
            return jax.lax.with_sharding_constraint(x, lead)
        return normal(ks[0]), normal(ks[1])

    x, w = operands(key)

    def with_gradients(layer_fn):
        def run(x, w_gu, w_down, w_router, w):
            y, back = jax.vjp(
                lambda x, gu, down: layer_fn(x, gu, down, w_router),
                x, w_gu, w_down)
            return (y, *back(w.astype(y.dtype)))
        return jax.jit(run)

    def exact_side(parts=(), rnd=ref.exact):
        """The reference's layer, one row of the batch at a time (every
        expert on every token: all rows at once would not fit), the
        matrices' gradients summed over the rows."""
        def fn(x, gu, down, w_router):
            y, _counts = ref.sparse_ffn(
                (gu, down, w_router), x[None], top_k=top_k, rnd=rnd,
                parts=tuple(parts), groups=mesh.shape[axis])
            return y[0]
        run = with_gradients(fn)
        outs = [run(x[r * seq:(r + 1) * seq], w_gu, w_down, w_router,
                    w[r * seq:(r + 1) * seq]) for r in range(rows)]
        ys, dxs, d_gus, d_downs = zip(*outs)
        return (jnp.concatenate(ys), jnp.concatenate(dxs), sum(d_gus),
                sum(d_downs))

    if parts is None and rnd is None:
        a = tr["amp"]

        def program(x, gu, down, w_router):
            with mesh_plan(mesh, (axis,), axis), amp.auto_cast(
                    enable=a["level"] != "O0", level=a["level"],
                    dtype=a["dtype"]):
                weights_, experts = moe_route.op_def.fn(
                    x, w_router, top_k, 1.0, "softmax")
                y, _counts = moe_experts.op_def.fn(
                    x, weights_, experts, gu, down)
            return y
        got = with_gradients(program)(x, w_gu, w_down, w_router, w)
    else:
        got = exact_side(parts or (), rnd or ref.exact)
    want = exact_side()
    return {name: float(jnp.linalg.norm(
        (a.astype(jnp.float32) - b).ravel()) / jnp.linalg.norm(b.ravel()))
        for name, a, b in zip(EXCHANGE_OUTPUTS, got, want)}


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """`laguna_train_window.compare`, and `exchange_gap`, the worst of
    `exchange_gaps`, where the program's side has read them."""
    out = laguna_train_window.compare(prog, ref, limits)
    if "exchange_gaps" in prog:
        out["exchange_gap"] = {"value": max(prog["exchange_gaps"].values()),
                               "limit": limits["exchange_gap"]}
    return out


def reference_steps(cfg, mix, seed, ref, n_steps, rnd=None, parts=()):
    """`laguna_train_window.reference_steps` with `parts` (what a
    deliberately broken copy gets wrong)."""
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
    step = build_step(cfg, ctx.seed, ref, mix["batch"])
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
    chips = cell["chips"]
    counts = [np.asarray(c) for c in step.counts[:len(steps)]]
    loads = [observe_expert_load(c, assignments) for c in counts]
    moe = {k: float(np.mean([load[k] for load in loads]))
           for k in loads[0]} if loads else {}
    held = [float(c.sum(axis=1).mean() / assignments) for c in counts]
    if counts:      # chip 0's experts' share of a layer's assignments
        moe["moe.assignments_chip0"] = float(np.mean(
            [c[:, :c.shape[1] // chips].sum(axis=1).mean() / assignments
             for c in counts]))
    failed = sum(1 for _b, _e, loss in steps if not math.isfinite(loss))
    span = t1 - t0
    record = perf.compile_record("train_step") or {}
    exchange = mellum2_flops.exchange_bytes_per_step(cfg, mix, chips)
    ctx.window = {"kind": "train", "steps": steps, "t0": t0, "t1": t1,
                  "tokens_per_step": tokens, "chips": chips, "moe": moe,
                  "exchange_bytes_per_step": exchange,
                  "exchange_note": record.get("moe_exchange")}
    e2e = {"train_tok_s_chip": tokens * len(steps) / span / chips,
           "setup_s": setup_s}

    # the program's state leaves before the reference comes
    del step
    gc.collect()
    t_ref = clock()
    prog["exchange_gaps"] = exchange_gaps(cfg, mix, ctx.seed, ref)
    reference = reference_steps(cfg, mix, ctx.seed, ref, n_check)
    compared = compare(prog, reference, cell["limits"])
    names = [n for n, _s, _i in ref.param_specs(cfg)]
    notes = {"window_s": span, "steps": len(steps),
             "worst_leaves": worst_leaves(prog, reference, names),
             "check_steps": n_check, "exchange_gaps": prog["exchange_gaps"],
             "program_losses": prog["losses"],
             "reference_losses": reference["losses"],
             "moe": {**moe, **choices_differ(
                 first_counts, reference["held_counts"]),
                 # every expert answers: 1 by construction, at the
                 # window's first step and at its last
                 "assignments_held_first_last": held[:1] + held[-1:]},
             "exchange_bytes_per_step": exchange,
             "reference_s": clock() - t_ref,
             "paths": {k: record.get(k) for k in
                       ("moe", "moe_exchange", "attention",
                        "attention_window", "flash_causal", "rope",
                        "head_loss")}, **seen}
    correct = runlib.judge(compared) and not any(seen.values())
    return {"correct": correct, "attempted": len(steps), "failed": failed,
            "e2e": e2e, "peak": peak, "compared": compared, "notes": notes}
