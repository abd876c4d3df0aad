"""Records the small trace the reduction's test reads
(`tests/data/small.xplane.pb`): on the chip, four runs of a small jitted
program under `harness.*` spans with sleeps between them, so that the
trace has device operations, program runs, host spans and idle gaps.

    python benchmarks/tools/record_small_trace.py <output file>"""
import glob
import os
import shutil
import sys
import tempfile
import time

import _common  # noqa: F401  (puts the checkout on the path)


def main():
    import jax
    import jax.numpy as jnp
    from harness.runlib import annotate

    @jax.jit
    def small_step(x):
        for _ in range(3):
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small_step(x).block_until_ready()
    out = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    jax.profiler.start_trace(out)
    for _ in range(4):
        with annotate("harness.train.next_batch"):
            time.sleep(0.002)
        with annotate("harness.train.step"):
            y = small_step(x)
        with annotate("harness.train.read_loss"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    os.makedirs(os.path.dirname(os.path.abspath(sys.argv[1])), exist_ok=True)
    shutil.copy(found[0], sys.argv[1])
    shutil.rmtree(out, ignore_errors=True)
    print(sys.argv[1], os.path.getsize(sys.argv[1]), "bytes")


if __name__ == "__main__":
    main()
