"""Plain reference of the system's semantics, in NumPy alone.

Nothing here imports the program or reads what it made: it works on the
generator's dense matrix and baskets.

- ``frequent``: level-wise Apriori (join, prune by downward closure, count)
  with the store held as one bitmap column per item; an itemset's support is
  the popcount of the AND of its items' columns.
- ``rules``: every split A -> C of every frequent itemset of two or more items,
  kept when s(A u C) / s(A) reaches the minimum confidence, in float64.
- ``recommend``: per basket, the sum of the scores of the rules whose
  antecedent it holds, per consequent item; basket items are excluded and the
  best ``top_k`` are returned, ties broken by the lower item id.

``score_dtype`` rounds every rule score, for the control: the reference put
in the program's place at the precision below the one the configuration
states (bfloat16 scores summed in float32, as a matrix unit's default pass
would).
"""

from __future__ import annotations

import dataclasses
import math
from itertools import combinations

import numpy as np


def bitmap_columns(db: np.ndarray) -> np.ndarray:
    """(N items, ceil(rows / 8)) uint8: one bitmap of transactions per item."""
    return np.packbits(np.ascontiguousarray(db.T.astype(bool)), axis=1)


def supports(columns: np.ndarray, cands: list[tuple], block: int = 2048) -> np.ndarray:
    out = np.zeros(len(cands), np.int64)
    for s in range(0, len(cands), block):
        idx = np.asarray(cands[s:s + block], np.int64)
        acc = columns[idx[:, 0]].copy()
        for j in range(1, idx.shape[1]):
            acc &= columns[idx[:, j]]
        out[s:s + block] = np.bitwise_count(acc).sum(axis=1, dtype=np.int64)
    return out


def apriori_gen(prev: list[tuple]) -> list[tuple]:
    """Join itemsets that share all but their last item, then drop every
    candidate with an infrequent subset one item smaller."""
    prev_set = set(prev)
    groups: dict[tuple, list[int]] = {}
    for s in sorted(prev):
        groups.setdefault(s[:-1], []).append(s[-1])
    out = []
    for prefix, lasts in groups.items():
        for i, a in enumerate(lasts):
            for b in lasts[i + 1:]:
                c = prefix + (a, b)
                if all(c[:j] + c[j + 1:] in prev_set for j in range(len(c) - 2)):
                    out.append(c)
    return out


@dataclasses.dataclass
class Mined:
    counts: dict          # itemset tuple -> support
    candidates: dict      # level k -> number of candidates counted
    min_count: int
    rows: int


def frequent(db: np.ndarray, min_support: float, max_k: int) -> Mined:
    rows, num_items = db.shape
    min_count = max(1, math.ceil(min_support * rows))
    columns = bitmap_columns(db)
    counts, cand_n = {}, {}
    level = [(i,) for i in range(num_items)]
    for k in range(1, max_k + 1):
        if not level:
            break
        cand_n[k] = len(level)
        sup = supports(columns, level)
        keep = [(c, int(s)) for c, s in zip(level, sup) if s >= min_count]
        if not keep:
            break
        counts.update(keep)
        level = apriori_gen([c for c, _ in keep]) if k < max_k else []
    return Mined(counts, cand_n, min_count, rows)


@dataclasses.dataclass
class Rules:
    ante: list            # antecedent tuples
    cons: list            # consequent tuples
    score: np.ndarray     # (R,) confidence


def rules(mined: Mined, min_confidence: float) -> Rules:
    ante, cons, score = [], [], []
    for itemset, sup in mined.counts.items():
        k = len(itemset)
        for r in range(1, k):
            for a in combinations(itemset, r):
                conf = sup / mined.counts[a]
                if conf >= min_confidence:
                    ante.append(a)
                    cons.append(tuple(x for x in itemset if x not in a))
                    score.append(conf)
    return Rules(ante, cons, np.asarray(score, np.float64))


def recommend(rb: Rules, baskets: list, num_items: int, top_k: int,
              score_dtype=np.float64, acc_dtype=np.float64, block: int = 64):
    """(items (B, top_k), scores (B, top_k), every item's score (B, N)) per basket.

    Rule scores are rounded to ``score_dtype`` and summed in ``acc_dtype``."""
    by_len: dict[int, list[int]] = {}
    for i, a in enumerate(rb.ante):
        by_len.setdefault(len(a), []).append(i)
    groups = [(np.asarray([rb.ante[i] for i in idx], np.int64), np.asarray(idx))
              for idx in by_len.values()]
    cons_len = np.asarray([len(c) for c in rb.cons], np.int64)
    cons_off = np.concatenate([[0], np.cumsum(cons_len)])
    cons_items = np.asarray([x for c in rb.cons for x in c], np.int64)
    score = rb.score.astype(score_dtype).astype(acc_dtype)
    items_out = np.zeros((len(baskets), top_k), np.int64)
    scores_out = np.zeros((len(baskets), top_k), acc_dtype)
    acc_out = np.zeros((len(baskets), num_items), acc_dtype)
    for s in range(0, len(baskets), block):
        part = baskets[s:s + block]
        held = np.zeros((len(part), num_items), bool)
        for b, basket in enumerate(part):
            held[b, np.asarray(basket, np.int64)] = True
        acc = np.zeros((len(part), num_items), acc_dtype)
        for ante, idx in groups:
            b, r = np.nonzero(held[:, ante].all(axis=2))
            rule = idx[r]
            n = cons_len[rule]
            rows = np.repeat(b, n)
            cols = cons_items[np.repeat(cons_off[rule], n) + _ranks(n)]
            np.add.at(acc, (rows, cols), np.repeat(score[rule], n))
        acc[held] = -np.inf
        order = np.lexsort((np.broadcast_to(np.arange(num_items), acc.shape), -acc), axis=1)
        top = order[:, :top_k]
        items_out[s:s + len(part)] = top
        scores_out[s:s + len(part)] = np.take_along_axis(acc, top, axis=1)
        acc_out[s:s + len(part)] = acc
    return items_out, scores_out, acc_out


def _ranks(sizes: np.ndarray) -> np.ndarray:
    return np.arange(int(sizes.sum())) - np.repeat(np.cumsum(sizes) - sizes, sizes)
