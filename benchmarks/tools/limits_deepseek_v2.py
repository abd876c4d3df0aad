"""`tools/limits_qwen3next.py` for the DeepSeek-V2 cells: the same
readings (the program's first steps on each seed through ONE TrainStep,
the plain reference's, and on the first <control seeds> the fp8
control's: the nearest precision below the bfloat16 the configuration
states, in the step and in `mla_gap`'s core alike), and on the first
seed the controls that get a part of the mathematics wrong
(`deepseek_v2_reference`'s `parts`: the rotary key a head instead of
shared, the scale without mscale^2, the latent's norm left out, weights
normalised over the chosen, the balance loss left out), each of which
`correct` has to refuse. `mla_gap` is read on every side whose core
differs from the reference's: the program's, the fp8 control's, and the
two broken cores'. Beside the numbers compared: the losses, the balance
terms and, of the first batch, the held share, the busiest held expert
and the share of assignments that moved.

    python benchmarks/tools/limits_deepseek_v2.py <workload> <first seed> <seeds> <control seeds> [controls,...]

`controls`: which of `CONTROLS` to read (all, unless named)."""
import gc
import sys

import numpy as np

import _common
from limits_qwen3next import reset

PARTS = ("shared_rotary_key", "mscale", "latent_norm", "weights_as_scored",
         "balance_loss")
CORE_PARTS = ("shared_rotary_key", "mscale")    # what `mla_gap` can see
CONTROLS = ("fp8",) + PARTS


def main():
    workload, first, n, n_control = sys.argv[1], *map(int, sys.argv[2:5])
    controls = sys.argv[5].split(",") if len(sys.argv) > 5 else CONTROLS
    spec, cell, cfg, mix, ref = _common.start(workload)
    tw = spec.module("drivers", mix["driver"])
    out = f"limits.{workload}.jsonl"
    seeds = [first + 1000003 * i for i in range(n)]
    step = tw.build_step(cfg, seeds[0], ref)
    prog, chose, balance = {}, {}, {}
    for i, seed in enumerate(seeds):
        if i:       # the first seed's weights came with build_step
            reset(step, cfg, seed, ref)
        step.counts.clear()
        prog[seed] = tw.first_steps(step, cfg, mix, seed, ref,
                                    ref.CHECK_STEPS)
        chose[seed] = np.asarray(step.counts[0][0])
        balance[seed] = [float(np.asarray(b)) for _c, b in step.counts]
    del step
    gc.collect()
    for seed in seeds:      # the step's state has left the chip
        prog[seed]["mla_gaps"] = tw.mla_gaps(cfg, mix, seed, ref)
    names = [n for n, _s, _i in ref.param_specs(cfg)]
    limits = {"loss": float("inf"), "mla_gap": float("inf"),
              **cell["limits"]}
    assignments = mix["batch"] * mix["seq"] * cfg["num_experts_per_tok"]

    def say(side, seed, got, exact, **more):
        cmp = tw.compare(got, exact, limits)
        _common.say(out, seed=seed, side=side, losses=got["losses"],
                    worst=tw.worst_leaves(got, exact, names),
                    mla_gaps=got.get("mla_gaps"),
                    **{k: v["value"] for k, v in cmp.items()}, **more)

    def control(seed, **kw):
        """The reference's first steps with `kw` (a rounding, a part
        wrong), and `mla_gaps` where that reaches the core."""
        got = tw.reference_steps(cfg, mix, seed, ref, ref.CHECK_STEPS, **kw)
        parts = [p for p in kw.get("parts", ()) if p in CORE_PARTS]
        if parts or "rnd" in kw:
            gc.collect()
            got["mla_gaps"] = tw.mla_gaps(cfg, mix, seed, ref, parts=parts,
                                          rnd=kw.get("rnd"))
        return got

    for i, seed in enumerate(seeds):
        t0 = tw.clock()
        exact = tw.reference_steps(cfg, mix, seed, ref, ref.CHECK_STEPS)
        counts = chose[seed]
        say("program", seed, prog[seed], exact,
            reference_s=tw.clock() - t0, balance=balance[seed],
            reference_balance=exact["balance"],
            held_share=(counts.sum(1) / assignments).tolist(),
            load_max_over_mean=(counts.max(1) / counts.mean(1)).tolist(),
            **tw.choices_differ(counts, exact["held_counts"]))
        gc.collect()    # a Trainer is a cycle: its weights go only here
        if i < n_control and "fp8" in controls:
            say("control_fp8", seed, control(seed, rnd=ref.fp8), exact)
        if i == 0:
            for part in PARTS:
                if part in controls:
                    gc.collect()
                    say("control_" + part, seed,
                        control(seed, parts=(part,)), exact)
        del exact
        gc.collect()


if __name__ == "__main__":
    main()
