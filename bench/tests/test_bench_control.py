"""The control, one precision below the configuration's, comes out as not correct."""

from __future__ import annotations

import pytest

from bench import cells, control
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("control")))


@pytest.mark.parametrize("name", ["mine.tiny", "serve.tiny.steady"])
@pytest.mark.parametrize("seed", [2**31 + 5, 2**33 + 1, 7])
def test_control_fails_a_limit(root, name, seed):
    cell = cells.load(name, root)
    readings = control.readings(cell, seed)
    limits = cell.config["limits"]
    failed = [k for k, v in readings.items() if v > limits.get(k, 0)]
    assert failed, readings
