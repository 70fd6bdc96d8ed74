"""Where everything of one cell is found, by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; each lives in a file of its
own, and each metric in BENCHMARK.json is read by ``bench/metrics/<name>.py``
or, where that file is missing, by the reader of its stem: the name up to
its first ``.``, so that ``step_mfu.chat`` and ``step_mfu.batch`` share
``step_mfu.py`` and a later ``step_mfu.<kind>.py`` can read otherwise.
Adding a cell, a configuration, a traffic mix or a metric is therefore new
files plus new entries in BENCHMARK.json: nothing here names any of them.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path


class Spec:
    """BENCHMARK.json and the files it names, under checkout ``root``."""

    def __init__(self, root):
        self.root = Path(root)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise FileNotFoundError(f"no BENCHMARK.json at {self.root}")
        self.data = json.loads(path.read_text())
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.workloads = {w["name"]: w for w in self.data["workloads"]}
        self.metrics = {m["name"]: m for m in
                        self.data["end_to_end"] + self.data["per_layer"]}

    def workload(self, name: str) -> dict:
        if name not in self.workloads:
            raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                           f"{sorted(self.workloads)}")
        return self.workloads[name]

    def config(self, workload: dict) -> dict:
        entry = self.configs[workload["config"]]
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, workload: dict) -> dict:
        return json.loads(self.traffic_path(workload["traffic"]).read_text())

    def traffic_path(self, name: str) -> Path:
        return self.root / "bench" / "traffic" / f"{name}.json"

    def metric_path(self, name: str) -> Path:
        own = self.root / "bench" / "metrics" / f"{name}.py"
        if own.is_file():
            return own
        return own.with_name(name.split(".", 1)[0] + ".py")

    def cell_metrics(self, workload: dict, kind: str) -> list:
        """The metrics a cell reports: ``kind`` is 'end_to_end' or
        'per_layer'. A metric without a ``workloads`` list is every cell's
        that reports the end-to-end metric it moves."""
        name = workload["name"]
        e2e = [m["name"] for m in self.data["end_to_end"]
               if name in m.get("workloads", [name])]
        if kind == "end_to_end":
            return e2e
        return [m["name"] for m in self.data["per_layer"]
                if (name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str):
        """The ``read(run)`` function of the metric's reader."""
        return load_module(self.metric_path(metric), f"metric_{metric}").read


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
