"""`tools/limits_jamba.py` for the ZAYA1 cells: the same readings (the
program's first steps on each seed through ONE TrainStep, the plain
reference's, and the fp8 control's on the first <control seeds>), and on
the first seed the controls that leave a part of the mathematics out
(`zaya_reference.block`'s `parts`: the value shift, either convolution,
the depth averaging), each of which `correct` has to refuse. Beside the
numbers compared: the losses' relative gaps and, of the first batch, the
share of tokens whose choice differs from the reference's.

    python benchmarks/tools/limits_zaya.py <workload> <first seed> <seeds> <control seeds> [parts,...]"""
import gc
import sys

import numpy as np

import _common

PARTS = ("value_shift", "conv_dw", "conv_group", "depth_averaging")


def reset(step, cfg, seed, ref):
    """`limits_train.reset` with the reference's own seeded weights."""
    import jax.numpy as jnp
    from harness import zaya_reference
    shapes = [{k: (v.shape, v.dtype) for k, v in st.items()}
              for st in step.opt_states]
    step.params = step.opt_states = None
    gc.collect()
    step.params = zaya_reference.make(seed, ref.param_specs(cfg),
                                      jnp.float32)
    step.opt_states = [
        {k: (jnp.ones(s, d) if k.endswith("_pow") else jnp.zeros(s, d))
         for k, (s, d) in st.items()} for st in shapes]
    step._step_count = 0


def main():
    workload, first, n, n_control = sys.argv[1], *map(int, sys.argv[2:5])
    parts = sys.argv[5].split(",") if len(sys.argv) > 5 else PARTS
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
        chose[seed] = [np.asarray(a) for a in step.counts[0]]
    del step
    gc.collect()
    names = [n for n, _s, _i in ref.param_specs(cfg)]
    limits = {"loss": float("inf"), **cell["limits"]}

    def say(side, seed, got, exact, **more):
        cmp = tw.compare(got, exact, limits)
        _common.say(out, seed=seed, side=side, losses=got["losses"],
                    worst=tw.worst_leaves(got, exact, names),
                    **{k: v["value"] for k, v in cmp.items()}, **more)

    for i, seed in enumerate(seeds):
        t0 = tw.clock()
        exact = tw.reference_steps(cfg, mix, seed, ref, ref.CHECK_STEPS)
        counts, top, choice = chose[seed]
        say("program", seed, prog[seed], exact,
            reference_s=tw.clock() - t0,
            held_share=(counts.sum(1) / choice.shape[1]).tolist(),
            load_max_over_mean=(counts.max(1) / counts.mean(1)).tolist(),
            top1_weight_mean=top.tolist(),
            choices_differ_share=np.mean(
                choice != exact["choices"], axis=1).tolist())
        gc.collect()    # a Trainer is a cycle: its weights go only here
        if i < n_control:
            say("control_fp8", seed, tw.reference_steps(
                cfg, mix, seed, ref, ref.CHECK_STEPS, rnd=ref.fp8), exact)
        if i == 0:
            for part in parts:
                if part:
                    gc.collect()
                    say("control_without_" + part, seed, tw.reference_steps(
                        cfg, mix, seed, ref, ref.CHECK_STEPS,
                        parts=(part,)), exact)
        del exact
        gc.collect()


if __name__ == "__main__":
    main()
