"""`tools/limits_train.py` for a training cell whose driver is not
`train_window`: the same readings (the program's first steps on each
seed through ONE TrainStep, the plain reference's, and the fp8
control's on the first <control seeds>), through the driver the cell's
traffic mix names.

    python benchmarks/tools/limits_jamba.py <workload> <first seed> <seeds> <control seeds>"""
import gc
import sys

import _common
from limits_train import reset


def main():
    workload, first, n, n_control = sys.argv[1], *map(int, sys.argv[2:5])
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
    for i, seed in enumerate(seeds):
        t0 = tw.clock()
        exact = tw.reference_steps(cfg, mix, seed, ref, ref.CHECK_STEPS)
        seconds = tw.clock() - t0
        cmp = tw.compare(prog[seed], exact, cell["limits"])
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
                        worst=tw.worst_leaves(control, exact, names),
                        **{k: v["value"] for k, v in cmp.items()})
        del exact
        gc.collect()


if __name__ == "__main__":
    main()
