"""Every cell loads from its files, and a cell can be added from files alone."""

from __future__ import annotations

import hashlib
import os
import re

import pytest

from bench import cells
from bench.tests import tiny

SPEC = cells.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_from_its_files(name):
    cell = cells.load(name)
    assert cell.chips in (1, 4)
    assert cell.config["name"] == cell.config_name
    assert hasattr(cell.job_module(), "Job")
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in reported
        assert callable(cell.reader(m).read)


def test_benchmark_file_keeps_the_contract():
    assert SPEC["command"] == ["python3", "bench/run.py"] and SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[key]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(cells.ROOT, c["file"]))
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]
    layers = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert set(m["workloads"]) <= layers
        assert os.path.exists(os.path.join(cells.ROOT, "bench", "metrics", m["name"] + ".py"))


def _digest(root):
    h = hashlib.sha256()
    for base, _, files in sorted(os.walk(os.path.join(root, "bench"))):
        if "__pycache__" in base:
            continue
        for f in sorted(files):
            with open(os.path.join(base, f), "rb") as fh:
                h.update(f.encode() + fh.read())
    with open(os.path.join(root, "BENCHMARK.json"), "rb") as fh:
        h.update(fh.read())
    return h.hexdigest()


def test_a_cell_added_from_files_alone_loads(tmp_path):
    before = _digest(cells.ROOT)
    root = tiny.make_root(str(tmp_path))
    for name in tiny.CELLS:
        cell = cells.load(name, root)
        assert cell.config["quest"]["D"] == tiny.TINY["quest"]["D"]
        assert [m["name"] for m in cell.per_layer]
        for m in cell.per_layer:
            assert callable(cell.reader(m).read)
    assert _digest(cells.ROOT) == before


def test_the_added_closed_loop_cell_runs(tmp_path):
    root = tiny.make_root(str(tmp_path))
    out = tiny.run(root, "serve.tiny.batch")
    assert out["correct"] and out["metrics"]["serve_throughput_rps"]["value"] > 0
    traced = tiny.run(root, "serve.tiny.batch", traced=True)
    assert "batch_occupancy.serve_throughput" in traced["metrics"]
    assert traced["device"]["window_s"] > 0


@pytest.mark.parametrize("name", list(tiny.CELLS))
def test_a_counted_window_does_its_count_and_answers_all(tmp_path, name):
    root = tiny.make_root(str(tmp_path))
    out = tiny.run(root, name)
    assert out["correct"], out["checks"]
    assert out["attempted"] == tiny.COUNT[name] and out["failed"] == 0
