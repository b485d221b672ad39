"""The generator, and the plain reference against the program at a small Quest size."""

from __future__ import annotations

from itertools import combinations

import numpy as np
import pytest

from bench import compare, quest, reference

SMALL = quest.Quest(transactions=1500, items=48, avg_len=8, pattern_len=3, patterns=30,
                    pattern_seed=11)
MIN_SUPPORT, MIN_CONF, TOP_K = 0.03, 0.4, 5


@pytest.fixture(scope="module")
def data():
    db, baskets = quest.store_and_queries(SMALL, seed=2**33 + 7, queries=80)
    mined = reference.frequent(db, MIN_SUPPORT, 6)
    return db, baskets, mined, reference.rules(mined, MIN_CONF)


def test_generator_is_seeded_and_follows_its_law():
    a, qa = quest.store_and_queries(SMALL, seed=2**33 + 7, queries=50)
    b, qb = quest.store_and_queries(SMALL, seed=2**33 + 7, queries=50)
    c, _ = quest.store_and_queries(SMALL, seed=2**33 + 8, queries=50)
    assert np.array_equal(a, b) and all(np.array_equal(x, y) for x, y in zip(qa, qb))
    assert not np.array_equal(a, c)
    assert a.shape == (SMALL.transactions, SMALL.items) and a.sum(1).min() >= 1
    assert 0.8 * SMALL.avg_len < a.sum(1).mean() < 1.3 * SMALL.avg_len
    assert len(qa) == 50 and all(x.size >= 1 and np.all(np.diff(x) > 0) for x in qa)


def test_reference_counts_match_a_brute_force_count(data):
    db, _, mined, _ = data
    held = db.astype(bool)
    for itemset, count in list(mined.counts.items())[::7]:
        assert held[:, list(itemset)].all(axis=1).sum() == count
    frequent2 = {c for c in mined.counts if len(c) == 2}
    singles = [c[0] for c in mined.counts if len(c) == 1]
    for pair in combinations(singles, 2):
        support = held[:, list(pair)].all(axis=1).sum()
        assert (support >= mined.min_count) == (pair in frequent2)


def test_program_mine_and_rulebook_agree_with_the_reference(data, tmp_path):
    from repro.core.apriori import AprioriConfig
    from repro.core.streaming import mine_streamed
    from repro.data.store import ingest_dense
    from repro.serving.rulebook import compile_rulebook

    db, _, mined, rules = data
    store = ingest_dense(db, str(tmp_path / "store"), shard_rows=512)
    res = mine_streamed(store, AprioriConfig(min_support=MIN_SUPPORT, max_k=6), chunk_rows=512)
    assert compare.itemset_mismatches(res.as_dict(), mined.counts) == 0
    rb = compile_rulebook(res, min_confidence=MIN_CONF, num_items=SMALL.items)
    found = compare.rulebook(vars(rb), rules, MIN_CONF)
    assert found["rule_mismatches"] == 0 and found["rulebook_malformed"] == 0
    assert found["rule_score_err"] < 1e-6


def test_program_answers_agree_with_the_reference(data):
    from repro.serving.recommend import recommend
    from repro.serving.rulebook import compile_rulebook
    from repro.core.apriori import AprioriConfig, mine

    db, baskets, _, rules = data
    res = mine(db, AprioriConfig(min_support=MIN_SUPPORT, max_k=6))
    rb = compile_rulebook(res, min_confidence=MIN_CONF, num_items=SMALL.items)
    got = recommend(rb, [b.tolist() for b in baskets], top_k=TOP_K, impl="jnp")
    _, want, acc = reference.recommend(rules, baskets, SMALL.items, TOP_K)
    found = compare.answers(got.items, got.scores, baskets, acc, want, MIN_CONF)
    assert found == {"bad_answers": 0, "score_gap": found["score_gap"], "item_gap": 0.0}
    assert found["score_gap"] < 1e-6
    assert (want[:, 0] > 0).any()


def test_comparisons_catch_a_wrong_answer(data):
    _, baskets, _, rules = data
    items, want, acc = reference.recommend(rules, baskets, SMALL.items, TOP_K)
    bad = items.copy()
    b = int(np.argmax(want[:, 0] - want[:, 1]))
    bad[b, 0] = bad[b, 1]
    assert compare.answers(bad, want, baskets, acc, want, MIN_CONF)["bad_answers"] == 1
    swapped = items.copy()
    swapped[b, [0, -1]] = swapped[b, [-1, 0]]
    assert compare.answers(swapped, want, baskets, acc, want, MIN_CONF)["item_gap"] > 0.1
