"""Comparisons of what the timed path produced with the plain reference.

Each returns numbers that ``bench/jobs`` pair with their limits. The
rulebook's columns are read in their documented layout (bit ``j`` of word
``w`` is item ``32*w + j``), with code of this file.
"""

from __future__ import annotations

import numpy as np


def itemset_mismatches(program: dict, reference: dict) -> int:
    """Itemsets missing, extra, or with another support."""
    keys = set(program) | set(reference)
    return sum(program.get(k) != reference.get(k) for k in keys)


def _item_tuples(words: np.ndarray, block: int = 1 << 15) -> list[tuple]:
    out = []
    shifts = np.arange(32, dtype=np.uint32)
    for s in range(0, words.shape[0], block):
        part = np.asarray(words[s:s + block], np.uint32)
        bits = ((part[:, :, None] >> shifts) & np.uint32(1)).astype(bool)
        rows, cols = np.nonzero(bits.reshape(part.shape[0], -1))
        cuts = np.searchsorted(rows, np.arange(1, part.shape[0]))
        out += [tuple(x.tolist()) for x in np.split(cols, cuts)]
    return out


def rulebook(columns: dict, reference_rules, min_floor: float) -> dict:
    """``columns``: ante_packed, cons_packed, ante_len, scores as arrays.
    Returns rule_mismatches (rules missing, extra or repeated),
    rulebook_malformed (rows whose length is not the popcount of their
    antecedent, or padding rows that are not inert) and rule_score_err (the
    largest relative gap of a served score from the reference confidence)."""
    ante_len = np.asarray(columns["ante_len"])
    scores = np.asarray(columns["scores"], np.float64)
    ante = _item_tuples(np.asarray(columns["ante_packed"]))
    cons = _item_tuples(np.asarray(columns["cons_packed"]))
    malformed = 0
    program = {}
    for a, c, n, s in zip(ante, cons, ante_len.tolist(), scores.tolist()):
        if n < 0:
            malformed += bool(a or c or s != 0.0)
            continue
        malformed += len(a) != n
        program[(a, c)] = program.get((a, c), ()) + (s,)
    ref = {(a, c): float(s) for a, c, s in zip(reference_rules.ante, reference_rules.cons,
                                               reference_rules.score)}
    mismatches = sum(len(v) != 1 for v in program.values()) + len(set(program) ^ set(ref))
    err = 0.0
    for key, got in program.items():
        if key in ref:
            want = ref[key]
            err = max(err, max(abs(g - want) for g in got) / max(abs(want), min_floor))
    return {"rule_mismatches": mismatches, "rulebook_malformed": malformed,
            "rule_score_err": err}


def answers(items: np.ndarray, scores: np.ndarray, baskets: list, ref_acc: np.ndarray,
            ref_scores: np.ndarray, floor: float) -> dict:
    """Served top-k answers against the reference's scores of every item.

    bad_answers: answers with a repeated item, an item outside the vocabulary,
    or an item of the basket. score_gap: the largest relative gap of a served
    score from the reference's score in the same slot. item_gap: the largest
    relative gap of the reference's score of the served item from the
    reference's score in that slot (ties may come in either order)."""
    bad, score_gap, item_gap = 0, 0.0, 0.0
    num_items = ref_acc.shape[1]
    for b, basket in enumerate(baskets):
        got_items, got = np.asarray(items[b], np.int64), np.asarray(scores[b], np.float64)
        want = np.asarray(ref_scores[b], np.float64)
        if (len(set(got_items.tolist())) != got_items.size or got_items.min() < 0
                or got_items.max() >= num_items or np.isin(got_items, basket).any()):
            bad += 1
            continue
        denom = np.maximum(np.abs(want), floor)
        score_gap = max(score_gap, float(np.max(np.abs(got - want) / denom)))
        item_gap = max(item_gap, float(np.max(np.abs(ref_acc[b, got_items] - want) / denom)))
    return {"bad_answers": bad, "score_gap": score_gap, "item_gap": item_gap}
