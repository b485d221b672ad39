"""The trace reduction on a small recorded trace, and the roofline counts by hand."""

from __future__ import annotations

import json
import os

import pytest

from bench import roofline, trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        return json.load(f)


def _window(events):
    (w,) = [(s, s + d) for _, n, s, d in events["host"] if n == trace.WINDOW]
    return w


def _covered(spans, a, b):
    """Time in [a, b] covered by any span, by elementary intervals."""
    points = sorted({a, b, *(min(max(x, a), b) for s in spans for x in s)})
    return sum(q - p for p, q in zip(points, points[1:])
               if any(s <= p and q <= e for s, e in spans))


def test_busy_time_is_the_union_of_op_intervals(recorded):
    a, b = _window(recorded)
    ops = recorded["devices"]["/device:TPU:0"]
    got = trace.reduce(recorded)
    assert got["window_ns"] == b - a
    assert got["busy_ns"] == pytest.approx(_covered([(s, s + d) for _, s, d in ops], a, b))
    assert 0 < got["busy_ns"] < got["window_ns"]


def test_op_time_by_short_name(recorded):
    got = trace.reduce(recorded)
    a, b = _window(recorded)
    count = sum(min(s + d, b) - max(s, a) for n, s, d in recorded["devices"]["/device:TPU:0"]
                if n.startswith("%support_count_pallas"))
    assert got["op_ns"]["support_count_pallas"] == pytest.approx(count)
    assert got["device_ops"][0][0] == "support_count_pallas"
    assert got["device_ops"][0][1] == pytest.approx(count / 1e9)


def test_idle_gaps_are_named_by_host_work(recorded):
    got = trace.reduce(recorded)
    gaps = got["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert all(g1[1] >= g2[1] for g1, g2 in zip(gaps, gaps[1:]))
    assert gaps[0][0].startswith("mine / ")
    a, b = _window(recorded)
    assert sum(g[1] for g in gaps) <= (got["window_ns"] - got["busy_ns"]) / 1e9 + 1e-12


@pytest.mark.parametrize("op,name", [
    ("%support_count_pallas.1 = s32[1,8192]{1,0} custom-call(...)", "support_count_pallas"),
    ("%rule_match_pallas = f32[64,1024]{1,0} custom-call(...)", "rule_match_pallas"),
    ("%copy-start.2 = (s32[1024]{0}) copy-start(...)", "copy-start"),
    ("fusion.12", "fusion"),
])
def test_short_names(op, name):
    assert trace.short_name(op) == name


def test_count_pass_by_hand():
    # 1,000 rows, 300 candidates, 100 items (4 words a row)
    ops, nbytes = roofline.count_pass(1000, 300, 100)
    assert ops == 2 * 1000 * 300 * 100 == 6.0e7
    assert nbytes == (1000 + 300) * 4 * 4 == 20800


def test_rule_match_by_hand():
    # 8 baskets against 1,000 rules of 1,000 items (32 words), one dispatch
    ops, nbytes = roofline.rule_match(8, 1000, 1000)
    assert ops == 1.6e7
    rulebook = 1000 * (32 * 4 + 32 * 4 + 4 + 4)
    assert nbytes == rulebook + 8 * (32 * 4 + 1000 * 4) == 297024


def test_share_names_its_bound():
    peak = roofline.peaks("TPU v5 lite")
    assert peak["int8_ops_per_s"] == 393e12 and peak["hbm_bytes_per_s"] == 819e9
    value, bound = roofline.share(*roofline.rule_match(8, 1000, 1000), 1e-6, peak)
    assert bound == "HBM bytes" and value == pytest.approx(100 * 297024 / 819e9 / 1e-6)
    value, bound = roofline.share(*roofline.count_pass(1000, 300, 100), 1e-6, peak)
    assert bound == "int8 operations" and value == pytest.approx(100 * 6e7 / 393e12 / 1e-6)
    with pytest.raises(KeyError):
        roofline.peaks("no such chip")
