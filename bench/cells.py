"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. The configuration's file is
the one its entry names; the mix is ``bench/traffic/<traffic>.json``; the job
kind the mix asks for is ``bench/jobs/<job>.py``; each per-layer metric is
``bench/metrics/<metric>.py``. Adding a configuration, a mix or a metric is
adding files and entries: nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    root: str

    def job_module(self):
        return importlib.import_module(f"bench.jobs.{self.traffic['job']}")

    def reader(self, metric: dict):
        """The per-layer metric's own reader module."""
        path = os.path.join(self.root, "bench", "metrics", metric["name"] + ".py")
        spec = importlib.util.spec_from_file_location(f"bench_metric_{metric['name']}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: str = ROOT) -> Cell:
    spec = benchmark(root)
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; there are {sorted(work)}")
    w = work[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"] if _listed(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)]
    return Cell(name, int(w["chips"]), w["config"], config, traffic,
                e2e, layer, root)
