"""Seconds of set-up that JAX spent on programs other than the step:
the rows of `perf.program_log()` between the run's two ends whose
compile family is not the step's, all four kinds (a function traced, a
jaxpr lowered, a module compiled or looked up, the persistent cache's
load), each instant once: a cache's load lies inside the `backend` event
around it. The model's own initialisers, the optimizer's accumulators,
the harness's `build` (its weights), `leaf_norms` and the leaf-by-leaf
draws of `change_norms`, and whatever else set-up asks JAX for, one
eager program an operation and shape."""


def read(run):
    share = run.spec.module("layer_metrics", "setup_named_share.train")
    found = share.records(run)
    if found is None:
        return None
    return share.union_s(share.between(
        run, share.programs(found[1], step=False)))
