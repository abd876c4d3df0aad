"""The readings a serving cell's limit is set from, in one process on the
chip:

    python benchmarks/tools/limits_serve.py <workload> <first seed> <seeds> <control seeds> <seconds>

One engine and one warm-up; for each seed the seed's weights are put into
it and a short window at the cell's own load is served and sampled as a
run samples it. Then the engine leaves and, seed by seed, the plain
reference reads the widest gap of the served tokens; for the first
<control seeds> the control (the reference in fp8) reads the gap of the
tokens it would put first."""
import gc
import sys

import _common


def main():
    workload, first, n, n_control = sys.argv[1], *map(int, sys.argv[2:5])
    seconds = float(sys.argv[5])
    spec, cell, cfg, mix, ref = _common.start(workload)
    sw = spec.module("drivers", "serve_window")
    import jax.numpy as jnp
    from harness import weights
    out = f"limits.{workload}.jsonl"
    seeds = [first + 1000003 * i for i in range(n)]
    engine = sw.build_engine(cfg, seeds[0], ref)
    sw.warm_up(engine, cfg, mix)
    specs = ref.param_specs(cfg)
    samples = {}
    for seed in seeds:
        arrays = weights.make(seed, specs, jnp.dtype(cfg["serving"]["dtype"]))
        for t, a in zip(engine._tensors, arrays):
            t._data = a
        events = sw.schedule(cfg, mix, seed, seconds)
        w = sw.measure(engine, events, mix, seconds)
        picked = sw.sample_requests([r for r in w["done"] if r.ok], seed,
                                    mix["check_requests"])
        samples[seed] = [(r.ev.prompt, w["outputs"][r.ev.rid])
                         for r in picked]
        _common.say(out, seed=seed, side="served", finished=len(w["done"]),
                    failed=sum(1 for r in w["done"] if not r.ok),
                    backlog_end=w["backlog"], tokens=w["tokens"],
                    window_s=w["t1"] - w["t0"],
                    past_cached_prefix=sum(1 for r in picked if r.cached))
        while engine.has_unfinished:     # drain before the next seed
            engine.step()
    del engine
    gc.collect()
    pad_to = sw.pad_len(mix)
    for i, seed in enumerate(seeds):
        model = ref.Model(cfg, seed, dtype=cfg["serving"]["dtype"])
        t0 = sw.clock()
        gap, n_tok = sw.logit_gaps(model, samples[seed], pad_to)
        _common.say(out, seed=seed, side="program", tokens=n_tok,
                    served_logit_gap_max=gap, reference_s=sw.clock() - t0)
        if i < n_control:
            control = ref.Model(cfg, seed, dtype=cfg["serving"]["dtype"],
                                rnd=ref.fp8)
            control.params = model.params       # one copy of the weights
            gap, n_tok = sw.logit_gaps(model, samples[seed], pad_to,
                                       chooser=control)
            _common.say(out, seed=seed, side="control_fp8", tokens=n_tok,
                        served_logit_gap_max=gap)
            del control
        del model
        gc.collect()


if __name__ == "__main__":
    main()
