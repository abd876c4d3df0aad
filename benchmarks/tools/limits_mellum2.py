"""`tools/limits_deepseek_v2.py` for the Mellum2 cells: the same readings
(the program's first steps on each seed through ONE TrainStep over the
cell's mesh, the plain reference's, and on the first <control seeds> the
fp8 control's: the nearest precision below the bfloat16 the
configuration states, in the step and in `exchange_gap`'s layer alike),
and on the first seed the controls that get a part of the mathematics
wrong (`mellum2_reference`'s `parts`: one chip's experts' part left out
of the sum, weights not normalised over the chosen, the window left off
a sliding layer, YaRN's factor left off a full layer), each of which
`correct` has to refuse. `exchange_gap` is read on every side whose
expert layer differs from the reference's: the program's, the fp8
control's, and the two broken layers'. Beside the numbers compared: the
losses and, of the first batch, the held share, the busiest expert and
the share of assignments that moved.

    python benchmarks/tools/limits_mellum2.py <workload> <first seed> <seeds> <control seeds> [controls,...] [steps]

`controls`: which of `CONTROLS` to read (all, unless named). With
`<seeds>` 0 the program is not run: the named controls alone, on the
first seed, against the exact reference. `steps`: the first steps every
side follows (the reference's `CHECK_STEPS`, unless given: a control that
the first gradient tells needs one)."""
import gc
import sys

import numpy as np

import _common

PARTS = ("chip_out", "weights_as_scored", "no_yarn_factor", "no_window")
LAYER_PARTS = ("chip_out", "weights_as_scored")  # what `exchange_gap` sees
CONTROLS = ("fp8",) + PARTS


def reset(step, cfg, seed, ref):
    """`limits_qwen3next.reset`, every array made again in the shards
    the step's own lie in."""
    import jax.numpy as jnp
    laid = [p.sharding for p in step.params]
    states = [{k: (v.shape, v.dtype, v.sharding) for k, v in st.items()}
              for st in step.opt_states]
    step.params = step.opt_states = None
    gc.collect()
    step.params = ref.make(seed, ref.param_specs(cfg), jnp.float32, laid)
    step.opt_states = [
        {k: (jnp.ones(s, d, device=at) if k.endswith("_pow")
             else jnp.zeros(s, d, device=at))
         for k, (s, d, at) in st.items()} for st in states]
    step._step_count = 0


def main():
    workload, first, n, n_control = sys.argv[1], *map(int, sys.argv[2:5])
    controls = sys.argv[5].split(",") if len(sys.argv) > 5 else CONTROLS
    spec, cell, cfg, mix, ref = _common.start(workload)
    steps = int(sys.argv[6]) if len(sys.argv) > 6 else ref.CHECK_STEPS
    tw = spec.module("drivers", mix["driver"])
    out = f"limits.{workload}.jsonl"
    seeds = [first + 1000003 * i for i in range(n)]
    prog, chose = {}, {}
    step = tw.build_step(cfg, seeds[0], ref, mix["batch"]) if seeds else None
    for i, seed in enumerate(seeds):
        if i:       # the first seed's weights came with build_step
            reset(step, cfg, seed, ref)
        step.counts.clear()
        prog[seed] = tw.first_steps(step, cfg, mix, seed, ref, steps)
        chose[seed] = np.asarray(step.counts[0])
    del step
    gc.collect()
    for seed in seeds:      # the step's state has left the chips
        prog[seed]["exchange_gaps"] = tw.exchange_gaps(cfg, mix, seed, ref)
    names = [n for n, _s, _i in ref.param_specs(cfg)]
    limits = {"loss": float("inf"), "exchange_gap": float("inf"),
              **cell["limits"]}
    assignments = mix["batch"] * mix["seq"] * cfg["num_experts_per_tok"]

    def say(side, seed, got, exact, **more):
        cmp = tw.compare(got, exact, limits)
        _common.say(out, seed=seed, side=side, losses=got["losses"],
                    worst=tw.worst_leaves(got, exact, names),
                    exchange_gaps=got.get("exchange_gaps"),
                    **{k: v["value"] for k, v in cmp.items()}, **more)

    def control(seed, **kw):
        """The reference's first steps with `kw` (a rounding, a part
        wrong), and `exchange_gaps` where that reaches the layer."""
        got = tw.reference_steps(cfg, mix, seed, ref, steps, **kw)
        parts = [p for p in kw.get("parts", ()) if p in LAYER_PARTS]
        if parts or "rnd" in kw:
            gc.collect()
            got["exchange_gaps"] = tw.exchange_gaps(
                cfg, mix, seed, ref, parts=parts, rnd=kw.get("rnd"))
        return got

    for i, seed in enumerate(seeds or [first]):
        t0 = tw.clock()
        exact = tw.reference_steps(cfg, mix, seed, ref, steps)
        if seed in prog:
            counts = chose[seed]
            say("program", seed, prog[seed], exact,
                reference_s=tw.clock() - t0,
                held_share=(counts.sum(1) / assignments).tolist(),
                load_max_over_mean=(counts.max(1)
                                    / counts.mean(1)).tolist(),
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
