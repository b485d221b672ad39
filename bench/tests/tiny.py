"""A benchmark of tiny cells in a directory of its own, made from files alone.

``make_root`` copies the repository's traffic mixes and metric readers and
adds a small Quest configuration, an open-loop mix and cell with its own
end-to-end metric and metric files, and a BENCHMARK.json naming the cells:
the same way a later change adds a configuration, a mix, a metric or a cell.
``run`` drives a cell for a fixed number of jobs or requests, so no test
waits on the clock.
"""

from __future__ import annotations

import copy
import json
import os
import shutil

from bench import cells

TINY = {
    "name": "quest.tiny",
    "source": "Agrawal and Srikant, VLDB 1994, Table 3 parameterisation, at a test size",
    "quest": {"D": 3000, "N": 64, "T": 8, "I": 3, "L": 40, "pattern_seed": 3},
    "mining": {"min_support": 0.03, "max_k": 6, "min_confidence": 0.4,
               "rule_score": "confidence"},
    "serving": {"top_k": 5},
    "limits": {"rule_score_err": 1e-04, "score_gap": 3e-04, "item_gap": 3e-04},
    "assumed": [],
    "reduced": ["D", "N", "L"],
}

CELLS = {
    "mine.tiny": "mine.jobs",
    "serve.tiny.steady": "serve.open_steady",
    "serve.tiny.batch": "serve.closed_batch",
}

COUNT = {"mine.tiny": 1, "serve.tiny.steady": 120, "serve.tiny.batch": 200}

OPEN_MIX = {  # a mix the repository does not have, added as a file
    "job": "serve", "loop": "open", "arrivals": "poisson", "rate_rps": 150,
    "baskets": "held-out transactions of the store's law, each cut to a length drawn "
               "uniformly from 1 to its size",
    "basket_pool": 400, "latency_limit_ms": 100, "trace_seconds": 0.5, "checked_answers": 60,
    "why": "test size",
}

TRAFFIC = {  # test-size loads for the repository's mixes
    "serve.closed_batch": {"callers": 16, "max_rps": 40000, "trace_seconds": 0.5,
                           "checked_answers": 60, "basket_pool": 6000},
}


def make_root(tmp: str, config: dict | None = None) -> str:
    repo = cells.benchmark()
    for sub in ("traffic", "metrics"):
        shutil.copytree(os.path.join(cells.ROOT, "bench", sub), os.path.join(tmp, "bench", sub))
    for name, over in TRAFFIC.items():
        path = os.path.join(tmp, "bench", "traffic", name + ".json")
        with open(path) as f:
            traffic = json.load(f)
        traffic.update(over)
        with open(path, "w") as f:
            json.dump(traffic, f)
    with open(os.path.join(tmp, "bench", "traffic", "serve.open_steady.json"), "w") as f:
        json.dump(OPEN_MIX, f)
    os.makedirs(os.path.join(tmp, "bench", "configs"))
    with open(os.path.join(tmp, "bench", "configs", "quest.tiny.json"), "w") as f:
        json.dump(config or TINY, f)
    spec = copy.deepcopy(repo)
    spec["configs"] = [{"name": "quest.tiny", "source": TINY["source"],
                        "file": "bench/configs/quest.tiny.json", "reduced": TINY["reduced"],
                        "why": "test size"}]
    spec["workloads"] = [{"name": n, "config": "quest.tiny", "traffic": t, "chips": 1,
                          "why": "test size"} for n, t in CELLS.items()]
    kinds = {"mine.jobs": "mine", "serve.open_steady": "serve_p95",
             "serve.closed_batch": "serve_throughput"}
    e2e = {"mine.jobs": "mine_s", "serve.open_steady": "serve_p95_ms",
           "serve.closed_batch": "serve_throughput_rps"}
    add_p95_metrics(tmp, spec)
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [n for n, t in CELLS.items() if e2e[t] == m["name"]]
    for m in spec["per_layer"]:
        m["workloads"] = [n for n, t in CELLS.items() if m["name"].endswith("." + kinds[t])]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return tmp


def add_p95_metrics(tmp: str, spec: dict) -> None:
    """The open-loop cell's end-to-end metric and per-layer metrics, added as
    entries and reader files, as a later change would add them."""
    metrics = os.path.join(tmp, "bench", "metrics")
    for base in ("batch_occupancy", "rule_match_roofline", "device_idle_share"):
        shutil.copy(os.path.join(metrics, f"{base}.serve_throughput.py"),
                    os.path.join(metrics, f"{base}.serve_p95.py"))
    spec["end_to_end"].append({"name": "serve_p95_ms", "unit": "ms", "better": "lower",
                               "bound": 0.05, "source": "host_clock", "workloads": []})
    spec["per_layer"] += [dict(m, name=m["name"].replace(".serve_throughput", ".serve_p95"),
                               moves="serve_p95_ms")
                          for m in spec["per_layer"] if m["name"].endswith(".serve_throughput")]


def run(root: str, name: str, seed: int = 5, traced: bool = False):
    """The harness's run on the CPU for the cell's ``COUNT`` of jobs or
    requests: no look for a chip, test peaks."""
    import time

    import jax

    from bench import harness

    peak = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    cell = cells.load(name, root)
    return harness.run(cell, seed, 0.0, traced, jax.devices()[:1], time.perf_counter(), peak,
                       count=COUNT[name])
