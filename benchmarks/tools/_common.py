"""What the tools share with a run: the checkout's caches, the chip."""
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
for p in (REPO, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def start(workload: str):
    """(spec, cell, cfg, mix, ref) of a cell, caches placed, chip checked."""
    import importlib.util
    s = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH_DIR, "run.py"))
    run = importlib.util.module_from_spec(s)
    s.loader.exec_module(run)
    from harness import runlib
    from harness.spec import Spec
    spec = Spec(REPO)
    w = spec.workload(workload)
    cell = {**spec.data("cells", w["name"]), **w}
    run.place_caches()
    runlib.require_tpu(cell["chips"])
    return (spec, cell, spec.data("configs", cell["config"]),
            spec.data("traffic", cell["traffic"]),
            spec.module("reference", cell["config"]))


def say(out_name: str, **record):
    line = json.dumps(record)
    print(line, flush=True)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, out_name), "a") as f:
        f.write(line + "\n")
