"""Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`.
Every test runs on the CPU at a tiny size: control flow, counts and the
comparison's verdicts, never a time."""
import importlib.util
import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH_DIR), BENCH_DIR,
          os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def rehearse(tmp_path, capsys):
    """rehearse(workload, seed=..., seconds=..., trace=0, **tiny) ->
    the result line of a CPU rehearsal run of the one command. The look
    for a chip is the only thing skipped."""
    import tiny

    def go(workload, seed=7, seconds=1.0, trace=0, **tiny_kw):
        repo = tiny.make_tiny_repo(str(tmp_path / "repo"), **tiny_kw)
        capsys.readouterr()
        load_run().main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        repo=repo, require_chip=False)
        out = capsys.readouterr().out.strip().splitlines()
        return json.loads(out[-1])

    return go
