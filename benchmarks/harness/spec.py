"""Finding things by name. A cell, a configuration, a traffic mix, a
reference, a per-layer reader and a kernel's cost function are each a
file of their own, found from the name `BENCHMARK.json` gives — there is
no registry or import list a newcomer would have to edit."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)


class Spec:
    """One checkout's benchmark: `BENCHMARK.json` and the files beside it."""

    def __init__(self, repo: str = REPO):
        self.repo = repo
        with open(os.path.join(repo, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self.dir = os.path.join(repo, self.doc["paths"][0])

    @staticmethod
    def path(root: str, kind: str, name: str, ext: str) -> str:
        path = os.path.join(root, kind, name + ext)
        if not os.path.isfile(path):
            raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
        return path

    def data(self, kind: str, name: str) -> dict:
        """A data file (configs, traffic, cells) of this checkout."""
        with open(self.path(self.dir, kind, name, ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        """A code file (drivers, reference, layer_metrics, kernel_costs)
        of the benchmark that is running."""
        path = self.path(BENCH_DIR, kind, name, ".py")
        modname = "bench_%s_%s" % (kind, "".join(
            c if c.isalnum() else "_" for c in name))
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def metrics(self, group: str, workload: str) -> list:
        """The `end_to_end` or `per_layer` entries this cell reports: an
        entry without a `workloads` list is every cell's."""
        return [m for m in self.doc[group]
                if workload in m.get("workloads", [workload])]
