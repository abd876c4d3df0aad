"""A hash of every training cell's lowered step (its StableHLO text), at
the tests' tiny size, on the CPU: run it in two checkouts and compare. A
change to shared code (`TrainStep`, the head and the criterion,
`recompute`, a layer) that leaves a cell's text the same has changed
nothing the compiler sees of that cell; no chip, two minutes.

    JAX_PLATFORMS=cpu python benchmarks/tools/step_text_hash.py [cell ...]

PR 41 read all six accepted cells the same on the parent and on the
change with it (PR 39 had such a script under a scratch directory, which
the next session did not find)."""
import hashlib
import json
import os
import sys
import tempfile

import _common  # noqa: F401  (puts the checkout on the path)

sys.path.insert(0, os.path.join(_common.BENCH_DIR, "tests"))


def main(cells):
    import jax
    import jax.numpy as jnp
    import tiny
    from harness.spec import Spec
    spec = Spec(tiny.make_tiny_repo(tempfile.mkdtemp(
        dir=os.environ.get("TMPDIR"))))
    out = {}
    for w in spec.doc["workloads"]:
        mix = spec.data("traffic", w["traffic"])
        if "train" not in mix["driver"] or (cells and w["name"] not in cells):
            continue
        cfg = spec.data("configs", w["config"])
        ref = spec.module("reference", w["config"])
        tw = spec.module("drivers", mix["driver"])
        step = tw.build_step(cfg, 3, ref)
        step = getattr(step, "step", step)      # a `Counted` holds one
        ids, labels = tw.batch(cfg, mix, 3, 0)
        text = step._step_fn.jit_fn.lower(
            step.params, step.opt_states, step.buffers,
            jax.random.PRNGKey(0), jnp.float32(1e-4), [ids, labels],
            {}).as_text()
        out[w["name"]] = [hashlib.sha256(text.encode()).hexdigest()[:16],
                          len(text)]
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main(sys.argv[1:])
