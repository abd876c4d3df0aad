"""The readings a training cell's limits are set from, in one process on
the chip (training's readings need no measured window):

    python benchmarks/tools/limits_train.py <workload> <first seed> <seeds> <control seeds>

For each seed the program's first steps (ONE TrainStep, its state put
back to the seed's weights each time), then the plain reference's, and
for the first <control seeds> of them the control's: the reference with
every matmul operand rounded to fp8. Each line gives the numbers
`correct` compares; the limits go above the program's largest and below
the control's smallest."""
import gc
import sys

import _common


def reset(step, cfg, seed, ref):
    import jax.numpy as jnp
    from harness import weights
    shapes = [{k: (v.shape, v.dtype) for k, v in st.items()}
              for st in step.opt_states]
    step.params = step.opt_states = None
    gc.collect()
    step.params = weights.make(seed, ref.param_specs(cfg), jnp.float32)
    step.opt_states = [
        {k: (jnp.ones(s, d) if k.endswith("_pow") else jnp.zeros(s, d))
         for k, (s, d) in st.items()} for st in shapes]
    step._step_count = 0


def main():
    workload, first, n, n_control = sys.argv[1], *map(int, sys.argv[2:5])
    spec, cell, cfg, mix, ref = _common.start(workload)
    tw = spec.module("drivers", "train_window")
    out = f"limits.{workload}.jsonl"
    seeds = [first + 1000003 * i for i in range(n)]
    step = tw.build_step(cfg, seeds[0], ref)
    prog = {}
    for i, seed in enumerate(seeds):
        if i:       # the first seed's weights came with build_step
            reset(step, cfg, seed, ref)
        prog[seed] = tw.first_steps(step, cfg, mix, seed, ref,
                                    ref.CHECK_STEPS)
    del step
    gc.collect()
    for i, seed in enumerate(seeds):
        t0 = tw.clock()
        exact = tw.reference_steps(cfg, mix, seed, ref, ref.CHECK_STEPS)
        seconds = tw.clock() - t0
        cmp = tw.compare(prog[seed], exact, cell["limits"])
        names = [n for n, _s, _i in ref.param_specs(cfg)]
        _common.say(out, seed=seed, side="program", reference_s=seconds,
                    losses=prog[seed]["losses"],
                    worst=tw.worst_leaves(prog[seed], exact, names),
                    **{k: v["value"] for k, v in cmp.items()})
        if i < n_control:
            control = tw.reference_steps(cfg, mix, seed, ref,
                                         ref.CHECK_STEPS, rnd=ref.fp8)
            cmp = tw.compare(control, exact, cell["limits"])
            _common.say(out, seed=seed, side="control_fp8",
                        losses=control["losses"],
                        **{k: v["value"] for k, v in cmp.items()})
        del exact
        gc.collect()


if __name__ == "__main__":
    main()
