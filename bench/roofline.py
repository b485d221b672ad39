"""The work of the algorithm, and the share of the roofline a kernel reaches.

The counts are those of the algorithm on its real operands, whatever
representation or kernel runs them, so no later change of kernel can make a
share read above 100%:

- a count pass over N rows for K candidates of I items: 2*N*K*I operations
  (the dense {0,1} formulation) and (N + K) * ceil(I/32) * 4 bytes (each row
  and candidate read once, as bits);
- a rule-match dispatch of B baskets against R rules: 2*B*R*I operations, and
  the rulebook's four columns (two bitset columns, lengths, scores), the
  baskets as bits and the (B, I) float32 scores in bytes.

Operations are held to the int8 peak, bytes to the HBM peak; the least time
is the larger of the two.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def words(items: int) -> int:
    return (items + 31) // 32


def count_pass(rows: int, cands: int, items: int) -> tuple[float, float]:
    return 2.0 * rows * cands * items, float((rows + cands) * words(items) * 4)


def rule_match(baskets: int, rules: int, items: int, dispatches: int = 1) -> tuple[float, float]:
    """``baskets`` real rows over ``dispatches`` dispatches of one rulebook."""
    rulebook = rules * (2 * words(items) * 4 + 4 + 4)
    per_basket = words(items) * 4 + items * 4
    return 2.0 * baskets * rules * items, float(dispatches * rulebook + baskets * per_basket)


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    t_ops, t_bytes = ops / peak["int8_ops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "int8 operations") if t_ops >= t_bytes else (t_bytes, "HBM bytes")


def share(ops: float, nbytes: float, seconds: float, peak: dict) -> tuple[float, str]:
    """Percent of the roofline reached in ``seconds`` of kernel time."""
    t, bound = least_time(ops, nbytes, peak)
    return 100.0 * t / seconds, bound
