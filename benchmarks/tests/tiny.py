"""A copy of the benchmark's data files at sizes a CPU test can run: the
same names (so every reference, driver and reader resolves as in a real
run), tiny widths and short schedules. Never a measurement.

`BENCHMARK.json` has no serving cell yet, so the copy also gets the two
of `tests/data/serving.json` (entries, cells, mixes, engine settings,
all at tiny size): the serving driver, its readers and its accounting
are tested here until a cell brings them to the chip."""
import json
import os

from harness.spec import REPO, Spec

TINY_MODEL = dict(vocab_size=512, real_vocab_size=500, hidden_size=64,
                  num_layers=2, num_heads=4, head_dim=16,
                  intermediate_size=256, max_position_embeddings=256,
                  initializer_range=0.15)  # wide, so that fp8 moves tokens
SERVING = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "serving.json")


def make_tiny_repo(dst: str, rates=None, limits=None) -> str:
    """Writes BENCHMARK.json and the data files under `dst`; returns it."""
    real = Spec(REPO)
    with open(SERVING) as f:
        serving = json.load(f)
    doc = json.loads(json.dumps(real.doc))
    for group in ("workloads", "end_to_end", "per_layer"):
        doc[group] += serving[group]
    bench = os.path.join(dst, doc["paths"][0])
    for kind in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bench, kind), exist_ok=True)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    for c in doc["configs"]:
        cfg = real.data("configs", c["name"])
        cfg.update(TINY_MODEL)
        cfg["serving"] = serving["serving"]
        _dump(bench, "configs", c["name"], cfg)
    for w in doc["workloads"]:
        if w["name"] in serving["cells"]:
            mix = serving["traffic"][w["traffic"]]
            mix["generator"].update(rates or {})
            cell = serving["cells"][w["name"]]
        else:
            mix = real.data("traffic", w["traffic"])
            mix.update(batch=2, seq=32, trace_after_s=0.2, trace_for_s=0.3)
            cell = real.data("cells", w["name"])
        _dump(bench, "traffic", w["traffic"], mix)
        cell["limits"] = {k: (limits or {}).get(k, 10.0)
                          for k in cell["limits"]}
        _dump(bench, "cells", w["name"], cell)
    return dst


def _dump(bench, kind, name, obj):
    with open(os.path.join(bench, kind, name + ".json"), "w") as f:
        json.dump(obj, f)
