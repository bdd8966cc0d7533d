"""The loader: ``BENCHMARK.json`` names cells, configurations and metrics;
everything that belongs to one of them is a file of its own under this
directory, found by that name.  A later PR adds a cell, a configuration or a
per-layer metric by adding files and entries; nothing here is edited."""
import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with all that its names lead to."""

    def __init__(self, name, root=ROOT):
        self.root = root
        self.bench = _load(os.path.join(root, "BENCHMARK.json"))
        rows = [w for w in self.bench["workloads"] if w["name"] == name]
        if len(rows) != 1:
            raise SystemExit("no workload %r in BENCHMARK.json (has: %s)" % (
                name, ", ".join(w["name"] for w in self.bench["workloads"])))
        self.row = rows[0]
        self.name = name
        self.chips = int(self.row["chips"])
        conf = [c for c in self.bench["configs"]
                if c["name"] == self.row["config"]]
        if len(conf) != 1:
            raise SystemExit("workload %s names config %r, which "
                             "BENCHMARK.json lacks" % (name, self.row["config"]))
        self.config = _load(os.path.join(root, conf[0]["file"]))
        here = os.path.join(root, "benchmark")
        self.dir = here
        self.traffic = _load(os.path.join(
            here, "traffic", self.row["traffic"] + ".json"))
        self.limits = _load(os.path.join(here, "limits", name + ".json"))
        self.peaks_table = _load(os.path.join(here, "peaks.json"))

    def peaks(self, device_kind):
        if device_kind not in self.peaks_table:
            raise SystemExit("no peaks for device kind %r in peaks.json: a "
                             "device that is not in the table is an error, "
                             "not a default" % device_kind)
        return self.peaks_table[device_kind]

    def _reports(self, metric):
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self._reports(m)]

    def reader(self, metric_name):
        """The reader function of one per-layer metric: its file
        ``metrics/<name>.json`` names a function of ``readers/<module>.py``
        and the arguments that make it this metric."""
        spec = _load(os.path.join(self.dir, "metrics", metric_name + ".json"))
        mod = importlib.import_module("benchmark.readers." + spec["module"])
        return getattr(mod, spec["function"]), spec.get("args", {})

    def flops(self):
        return importlib.import_module(
            "benchmark.flops." + self.config["family"])

    def entry(self):
        return importlib.import_module(
            "benchmark.entries." + self.traffic["entry"])
