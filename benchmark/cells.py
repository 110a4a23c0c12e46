"""From a cell's name to its files, by name alone. A later PR adds a cell,
a configuration, a traffic mix, a family or a per-layer metric by adding
files and entries to BENCHMARK.json; nothing here names any of them.
"""
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _load_module(path, name):
    if not os.path.exists(path):
        raise FileNotFoundError("no %s" % path)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


class Cell(object):
    """One entry of `workloads` with everything it resolves to."""

    def __init__(self, name, root=ROOT):
        bench = _load_json(os.path.join(root, "BENCHMARK.json"))
        entries = {w["name"]: w for w in bench["workloads"]}
        if name not in entries:
            raise KeyError("no workload %r in BENCHMARK.json (have %s)"
                           % (name, sorted(entries)))
        entry = entries[name]
        bdir = os.path.join(root, "benchmark")
        cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
        self.name = name
        self.root = root
        self.chips = int(entry["chips"])
        self.config = _load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = _load_json(os.path.join(
            bdir, "traffic", entry["traffic"] + ".json"))
        self.family = _load_module(
            os.path.join(bdir, "families", self.config["family"] + ".py"),
            "benchmark_family_" + self.config["family"])
        self.limits = _load_json(os.path.join(bdir, "limits", name + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _applies(m, name)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if _applies(m, name) and m["moves"] in reported]
        self.run_seconds = bench["run_seconds"]

    def layer_reader(self, metric_name):
        """The reader module of a per-layer metric: `read(record)` -> number
        or None."""
        return _load_module(
            os.path.join(self.root, "benchmark", "layer_metrics",
                         metric_name + ".py"),
            "benchmark_layer_metric_" + metric_name.replace(".", "_")
            .replace("-", "_"))

    def mesh_size(self):
        size = 1
        for n in (self.traffic.get("mesh_axes") or {}).values():
            size *= int(n)
        return size
