"""`tools/limits_qwen3next.py` for the Ouro cells: the same readings
(the program's first steps on each seed through ONE TrainStep, the plain
reference's, and on the first <control seeds> the fp8 control's), and on
the first seed the controls that leave a part of the mathematics out or
change it (`ouro_reference`'s `parts`: the final norm between passes, the
post-sublayer norms N2 and N4, p_t held constant under the gradient,
beta 0, three passes for four), each of which `correct` has to refuse by
at least one limit. Beside the numbers compared: the losses, the eight
numbers of the first step's aux on both sides and their gaps.

    python benchmarks/tools/limits_ouro.py <workload> <first seed> <seeds> <control seeds> [controls,...]

`controls`: which of `CONTROLS` to read (all, unless named)."""
import gc
import sys

import _common
from limits_qwen3next import reset

PARTS = ("final_norm", "post_norms", "gate_gradient", "entropy", "passes")
CONTROLS = ("fp8",) + PARTS


def main():
    workload, first, n, n_control = sys.argv[1], *map(int, sys.argv[2:5])
    controls = sys.argv[5].split(",") if len(sys.argv) > 5 else CONTROLS
    spec, cell, cfg, mix, ref = _common.start(workload)
    tw = spec.module("drivers", mix["driver"])
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
    names = [n for n, _s, _i in ref.param_specs(cfg)]
    limits = {"loss": float("inf"), **cell["limits"]}

    def say(side, seed, got, exact, **more):
        cmp = tw.compare(got, exact, limits)
        _common.say(out, seed=seed, side=side, losses=got["losses"],
                    worst=tw.worst_leaves(got, exact, names),
                    aux=got["aux"].tolist(),
                    pass_gaps=tw.pass_gaps(got["aux"], exact["aux"]),
                    **{k: v["value"] for k, v in cmp.items()}, **more)

    for i, seed in enumerate(seeds):
        t0 = tw.clock()
        exact = tw.reference_steps(cfg, mix, seed, ref, ref.CHECK_STEPS)
        say("program", seed, prog[seed], exact,
            reference_s=tw.clock() - t0, reference_aux=exact["aux"].tolist(),
            reference_losses=exact["losses"])
        gc.collect()    # a Trainer is a cycle: its weights go only here
        if i < n_control and "fp8" in controls:
            say("control_fp8", seed, tw.reference_steps(
                cfg, mix, seed, ref, ref.CHECK_STEPS, rnd=ref.fp8), exact)
        if i == 0:
            for part in PARTS:
                if part in controls:
                    gc.collect()
                    say("control_" + part, seed, tw.reference_steps(
                        cfg, mix, seed, ref, ref.CHECK_STEPS,
                        parts=(part,)), exact)
        del exact
        gc.collect()


if __name__ == "__main__":
    main()
