"""`tools/limits_zaya.py` for the Qwen3-Next cells: the same readings
(the program's first steps on each seed through ONE TrainStep, the plain
reference's, and on the first <control seeds> the fp8 control's and the
control with the gated delta rule in bfloat16, `rule_float32`, the
precision below the float32 the configuration states for it), and on
the first seed the controls that leave a part of the mathematics out
(`qwen3next_reference.block`'s `parts`: the delta rule's correction, the
attention's output gate, the delta rule's unit norms of q and k, `1 + w`
read as `w`), each of which `correct` has to refuse. `rule_gap` is read
on every side whose rule differs from the reference's: the program's,
and the control's in bfloat16. Beside the numbers compared: the losses' relative gaps and, of the first batch, the held
share, the busiest held expert and the share of assignments that moved.

    python benchmarks/tools/limits_qwen3next.py <workload> <first seed> <seeds> <control seeds> [controls,...]

`controls`: which of `CONTROLS` to read (all, unless named)."""
import gc
import sys

import numpy as np

import _common

PARTS = ("delta_correction", "output_gate", "qk_unit_norm", "zero_centered")
CONTROLS = ("fp8", "rule_float32") + PARTS


def reset(step, cfg, seed, ref):
    """`limits_train.reset` with the reference's own seeded weights."""
    import jax.numpy as jnp
    shapes = [{k: (v.shape, v.dtype) for k, v in st.items()}
              for st in step.opt_states]
    step.params = step.opt_states = None
    gc.collect()
    step.params = ref.make(seed, ref.param_specs(cfg), jnp.float32)
    step.opt_states = [
        {k: (jnp.ones(s, d) if k.endswith("_pow") else jnp.zeros(s, d))
         for k, (s, d) in st.items()} for st in shapes]
    step._step_count = 0


def main():
    workload, first, n, n_control = sys.argv[1], *map(int, sys.argv[2:5])
    controls = sys.argv[5].split(",") if len(sys.argv) > 5 else CONTROLS
    spec, cell, cfg, mix, ref = _common.start(workload)
    tw = spec.module("drivers", mix["driver"])
    out = f"limits.{workload}.jsonl"
    seeds = [first + 1000003 * i for i in range(n)]
    step = tw.build_step(cfg, seeds[0], ref)
    prog, chose = {}, {}
    for i, seed in enumerate(seeds):
        if i:       # the first seed's weights came with build_step
            reset(step, cfg, seed, ref)
        step.counts.clear()
        prog[seed] = tw.first_steps(step, cfg, mix, seed, ref,
                                    ref.CHECK_STEPS)
        chose[seed] = np.asarray(step.counts[0])
    del step
    gc.collect()
    for seed in seeds:      # the step's state has left the chip
        prog[seed]["rule_gaps"] = tw.rule_gaps(cfg, mix, seed, ref)
    names = [n for n, _s, _i in ref.param_specs(cfg)]
    limits = {"loss": float("inf"), "rule_gap": float("inf"),
              **cell["limits"]}
    assignments = mix["batch"] * mix["seq"] * cfg["num_experts_per_tok"]

    def say(side, seed, got, exact, **more):
        cmp = tw.compare(got, exact, limits)
        _common.say(out, seed=seed, side=side, losses=got["losses"],
                    worst=tw.worst_leaves(got, exact, names),
                    rule_gaps=got.get("rule_gaps"),
                    **{k: v["value"] for k, v in cmp.items()}, **more)

    for i, seed in enumerate(seeds):
        t0 = tw.clock()
        exact = tw.reference_steps(cfg, mix, seed, ref, ref.CHECK_STEPS)
        counts = chose[seed]
        say("program", seed, prog[seed], exact,
            reference_s=tw.clock() - t0,
            held_share=(counts.sum(1) / assignments).tolist(),
            load_max_over_mean=(counts.max(1) / counts.mean(1)).tolist(),
            **tw.choices_differ(counts, exact["held_counts"]))
        gc.collect()    # a Trainer is a cycle: its weights go only here
        if i < n_control and "fp8" in controls:
            say("control_fp8", seed, tw.reference_steps(
                cfg, mix, seed, ref, ref.CHECK_STEPS, rnd=ref.fp8), exact)
        if i < n_control and "rule_float32" in controls:
            gc.collect()
            low = ("rule_float32",)
            say("control_rule_bf16", seed, dict(
                tw.reference_steps(cfg, mix, seed, ref, ref.CHECK_STEPS,
                                   parts=low),
                rule_gaps=tw.rule_gaps(cfg, mix, seed, ref, parts=low)), exact)
        if i == 0:
            for part in PARTS:
                if part in controls:
                    gc.collect()
                    say("control_without_" + part, seed, tw.reference_steps(
                        cfg, mix, seed, ref, ref.CHECK_STEPS,
                        parts=(part,)), exact)
        del exact
        gc.collect()


if __name__ == "__main__":
    main()
