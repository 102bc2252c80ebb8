"""Finds every piece of a benchmark cell by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; each lives in a file of
its own (``configs/<config>.json``, ``traffic/<mix>.json``), and so do
the cell's correctness limits (``limits/<cell>.json``) and each
per-layer metric's reader (``metrics/<metric>.py``, a ``read(run)``
function). Adding a configuration, a mix or a metric means adding files
and entries; nothing here changes.
"""
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


class Catalog:
    def __init__(self, root=None):
        self.root = root or os.path.dirname(BENCH_DIR)
        self.bench = os.path.join(self.root, "bench")
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _json(self, *parts):
        with open(os.path.join(self.bench, *parts)) as f:
            return json.load(f)

    def names(self, kind):
        """Names of the files of one kind: configs, traffic, metrics."""
        ext = ".py" if kind == "metrics" else ".json"
        return sorted(n[:-len(ext)]
                      for n in os.listdir(os.path.join(self.bench, kind))
                      if n.endswith(ext) and not n.startswith("_"))

    def workload(self, name):
        """-> (cell entry, configuration, traffic mix, limits)."""
        for cell in self.spec["workloads"]:
            if cell["name"] == name:
                break
        else:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        return (cell, self._json("configs", f"{cell['config']}.json"),
                self._json("traffic", f"{cell['traffic']}.json"),
                self._json("limits", f"{name}.json"))

    def metrics(self, group, cell):
        """Entries of ``end_to_end`` or ``per_layer`` that this cell
        reports: those without a ``workloads`` list, and those listing it."""
        return [m for m in self.spec[group]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, metric):
        """The ``read(run)`` function of ``metrics/<metric>.py``."""
        path = os.path.join(self.bench, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
