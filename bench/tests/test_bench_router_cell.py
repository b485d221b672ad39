"""The replicated tier's cell, added to a tiny benchmark from files alone.

A four-replica router cell (the repository's ``serve.closed_replicated`` mix
at a test size, a tiny configuration with the repository's router settings)
is added beside ``tiny.make_root``'s cells, as the repository's own cell was
added. On the CPU the four replicas share its one device. A counted window
must come out correct with every request answered and checked answers from
every replica; a traced one must report the router's per-layer metrics; an
answer altered on one replica alone must make the run incorrect.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import os
import time
from concurrent.futures import Future

import pytest

from bench import cells
from bench.tests import tiny

CELL = "serve.tiny.router4"
COUNT = 400
NEW_METRICS = ("router_admit_us.router4", "router_complete_us.router4",
               "router_background_share.router4", "replica_imbalance.router4",
               "device_idle_share.router4")
MIX = {"callers": 64, "max_rps": 40000, "trace_seconds": 0.5, "checked_answers": 120,
       "basket_pool": 6000}


def _repo_config(name):
    spec = cells.benchmark()
    entry = {c["name"]: c for c in spec["configs"]}[name]
    with open(os.path.join(cells.ROOT, entry["file"])) as f:
        return json.load(f)


def add_router_cell(root: str) -> None:
    """The router configuration, mix and cell, added as files and entries."""
    router = _repo_config("quest.t20i6d100k.router4")
    config = dict(copy.deepcopy(tiny.TINY), name="quest.tiny.router4",
                  serving=dict(tiny.TINY["serving"], replicas=4,
                               router=router["serving"]["router"]))
    with open(os.path.join(root, "bench", "configs", "quest.tiny.router4.json"), "w") as f:
        json.dump(config, f)
    path = os.path.join(root, "bench", "traffic", "serve.closed_replicated.json")
    with open(path) as f:
        mix = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(mix, **MIX), f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "quest.tiny.router4", "source": tiny.TINY["source"],
                            "file": "bench/configs/quest.tiny.router4.json",
                            "reduced": tiny.TINY["reduced"], "why": "test size"})
    spec["workloads"].append({"name": CELL, "config": "quest.tiny.router4",
                              "traffic": "serve.closed_replicated", "chips": 4,
                              "why": "test size"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] == "serve_throughput_rps" or m["name"].endswith(".router4"):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tiny.make_root(str(tmp_path_factory.mktemp("router")))
    add_router_cell(path)
    return path


def run(root, traced=False, seed=5):
    import jax

    from bench import harness

    peak = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    return harness.run(cells.load(CELL, root), seed, 0.0, traced, jax.devices()[:1],
                       time.perf_counter(), peak, count=COUNT)


def test_router_cell_is_correct_and_checks_every_replica(root):
    out = run(root)
    assert out["correct"], out["checks"]
    assert out["attempted"] == COUNT and out["failed"] == 0
    assert out["checks"]["unanswered"]["value"] == 0
    assert out["checks"]["replicas_unchecked"] == {"value": 0, "limit": 0}
    assert out["metrics"]["serve_throughput_rps"]["value"] > 0
    assert "setup_s" in out["metrics"]


def test_traced_router_cell_reports_the_router_metrics(root):
    out = run(root, traced=True, seed=6)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    for name in NEW_METRICS:
        assert name in got, name
        assert got[name]["value"] >= 0.0, name
    assert got["replica_imbalance.router4"]["value"] >= 100.0
    assert got["router_admit_us.router4"]["value"] > 0.0
    assert got["router_complete_us.router4"]["value"] > 0.0


def test_an_answer_altered_on_one_replica_is_incorrect(root, monkeypatch):
    import repro.serving.router as router

    made = []

    class Planted(router.Gateway):
        """Replica 2 moves every answer's items one id up; the others answer
        as built."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.planted = len(made) == 2
            made.append(self)

        def submit(self, *a, **k):
            fut = super().submit(*a, **k)
            if not self.planted:
                return fut
            out = Future()

            def move(f):
                if f.exception() is not None:
                    out.set_exception(f.exception())
                else:
                    r = f.result()
                    out.set_result(dataclasses.replace(r, items=(r.items + 1) % self.num_items))

            fut.add_done_callback(move)
            return out

    monkeypatch.setattr(router, "Gateway", Planted)
    out = run(root, seed=7)
    assert len(made) == 4
    assert not out["correct"], out["checks"]
    assert out["checks"]["bad_answers"]["value"] > 0 or out["checks"]["item_gap"]["value"] > 3e-4


@pytest.mark.parametrize("key", ["quest", "mining", "limits"])
def test_router_config_serves_the_t20_deployment(key):
    router, t20 = _repo_config("quest.t20i6d100k.router4"), _repo_config("quest.t20i6d100k")
    assert router[key] == t20[key]
    assert router["serving"]["top_k"] == t20["serving"]["top_k"]
    assert router["reduced"] == [] and router["serving"]["replicas"] == 4
