"""IBM Quest synthetic transactions, vectorised (Agrawal & Srikant, VLDB 1994, §2.4.3).

Parameters, as the paper names them: |D| transactions over N items, average
transaction size |T|, |L| potentially large itemsets of average size |I|.

- The |L| itemsets are the deployment's catalogue. They are drawn from the
  configuration's own ``pattern_seed``, so every run seed mines a store of the
  same structure and the work per job does not swing with the seed. Each
  itemset's size is Poisson(|I|); a fraction of its items, exponential with
  mean 0.5 (the correlation level), comes from the itemset before it, the rest
  is uniform. Each has a weight, exponential with mean 1, and a corruption
  level, normal with mean 0.5 and variance 0.1.
- Transactions are drawn from the run seed. A transaction's size is
  Poisson(|T|). Itemsets are picked by weight and corrupted: items are dropped
  while a uniform draw is below the itemset's corruption level. Transactions
  are filled in turn; an itemset that does not fit is put in anyway in half
  the cases and moved to the next transaction otherwise.

Picks, corruption and item choice are array arithmetic; the one loop over
rows moves a cursor over the picks and touches no item.
"""

from __future__ import annotations

import bisect
import dataclasses

import numpy as np

CORRELATION = 0.5                      # mean share of an itemset taken from the one before
CORRUPTION_MEAN, CORRUPTION_VAR = 0.5, 0.1


@dataclasses.dataclass(frozen=True)
class Quest:
    transactions: int      # |D|
    items: int             # N
    avg_len: float         # |T|
    pattern_len: float     # |I|
    patterns: int          # |L|
    pattern_seed: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Quest":
        q = cfg["quest"]
        return cls(q["D"], q["N"], q["T"], q["I"], q["L"], q["pattern_seed"])


@dataclasses.dataclass(frozen=True)
class Catalogue:
    offsets: np.ndarray    # (L + 1,) start of each itemset in ``items``
    items: np.ndarray      # concatenated item ids
    weights: np.ndarray    # (L,) pick probabilities
    corruption: np.ndarray  # (L,) corruption levels in [0, 0.95]


def catalogue(q: Quest) -> Catalogue:
    rng = np.random.default_rng(q.pattern_seed)
    sets, prev = [], np.zeros(0, np.int64)
    for _ in range(q.patterns):
        size = min(max(1, int(rng.poisson(q.pattern_len))), q.items)
        n_prev = min(int(round(min(1.0, rng.exponential(CORRELATION)) * size)), prev.size)
        kept = rng.choice(prev, size=n_prev, replace=False) if n_prev else prev[:0]
        rest = np.setdiff1d(np.arange(q.items), kept)
        cur = np.concatenate([kept, rng.choice(rest, size=size - n_prev, replace=False)])
        sets.append(np.sort(cur))
        prev = cur
    weights = rng.exponential(1.0, q.patterns)
    corruption = np.clip(rng.normal(CORRUPTION_MEAN, np.sqrt(CORRUPTION_VAR), q.patterns),
                         0.0, 0.95)
    offsets = np.concatenate([[0], np.cumsum([s.size for s in sets])])
    return Catalogue(offsets, np.concatenate(sets), weights / weights.sum(), corruption)


def transactions(q: Quest, cat: Catalogue, rng: np.random.Generator, n: int):
    """``n`` transactions as (row ids, item ids) of their set bits."""
    sizes = np.maximum(1, rng.poisson(q.avg_len, n))
    coins = rng.random(n) < 0.5
    # enough picks to fill every transaction: E[kept] = size - sum_{j<=size} c**j
    set_size = np.diff(cat.offsets)
    expect = set_size - np.array([np.sum(c ** np.arange(1, s + 1)) for c, s in
                                  zip(cat.corruption, set_size)])
    need = sizes.sum() / max(float(cat.weights @ expect), 0.1)
    picks = rng.choice(q.patterns, size=int(1.1 * need + 10 * np.sqrt(need)) + 64, p=cat.weights)
    start, size = cat.offsets[picks], set_size[picks]
    # items are dropped while U < c: a geometric count, P(drops >= j) = c**j
    c = cat.corruption[picks]
    drops = np.floor(np.log1p(-rng.random(picks.size)) / np.log(np.maximum(c, 1e-12)))
    kept = np.maximum(0, size - np.where(c > 0, drops, 0)).astype(np.int64)
    # the kept items of a pick are those with the smallest random keys
    slot_pick = np.repeat(np.arange(picks.size), size)
    slot_item = cat.items[np.repeat(start, size) + _ranks(size)]
    order = np.argsort(slot_pick + rng.random(slot_pick.size))
    keep = _ranks(size) < np.repeat(kept, size)
    slot_pick, slot_item = slot_pick[order][keep], slot_item[order][keep]
    # fill transactions in turn; the cursor loop moves integers only
    cum = np.cumsum(kept).tolist()
    first = [0] * n
    j = filled = 0
    for t in range(n):
        while j < len(cum) and cum[j] == filled:
            j += 1                                  # picks that kept nothing
        first[t] = j
        target = filled + int(sizes[t])
        j = bisect.bisect_right(cum, target, lo=j)  # picks that fit whole
        if j == first[t] or (j < len(cum) and coins[t]):
            j += 1                                  # the one that does not fit
        if j > len(cum):
            raise ValueError("too few itemset picks drawn")
        filled = cum[j - 1]
    bounds = np.append(first, j)
    pick_row = np.concatenate([np.full(first[0], -1), np.repeat(np.arange(n), np.diff(bounds))])
    live = slot_pick < bounds[-1]
    return pick_row[slot_pick[live]], slot_item[live]


def _ranks(sizes: np.ndarray) -> np.ndarray:
    """0, 1, ..., s-1 for each s in ``sizes``, concatenated."""
    total = int(sizes.sum())
    return np.arange(total) - np.repeat(np.cumsum(sizes) - sizes, sizes)


def dense(rows: np.ndarray, items: np.ndarray, n: int, num_items: int) -> np.ndarray:
    out = np.zeros((n, num_items), dtype=np.int8)
    out[rows, items] = 1
    return out


def store_and_queries(q: Quest, seed: int, queries: int = 0):
    """The seed's store as a dense {0,1} int8 (|D|, N) matrix, and ``queries``
    held-out baskets: transactions of the same law, each cut to a length drawn
    uniformly from 1 to its size (a cart being filled)."""
    cat = catalogue(q)
    store_rng, query_rng = (np.random.default_rng(s)
                            for s in np.random.SeedSequence(seed).spawn(2))
    db = dense(*transactions(q, cat, store_rng, q.transactions), q.transactions, q.items)
    baskets = []
    if queries:
        extra = queries + queries // 8 + 16
        rows, items = _unique_pairs(*transactions(q, cat, query_rng, extra), q.items)
        # cut each row to a uniform length in [1, size]: keep its items of smallest random key
        sizes = np.bincount(rows, minlength=extra)
        cut = query_rng.integers(1, np.maximum(sizes, 1) + 1)
        order = np.lexsort((query_rng.random(rows.size), rows))
        keep = _ranks(sizes) < np.repeat(cut, sizes)
        rows, items = rows[order][keep], items[order][keep]
        order = np.lexsort((items, rows))
        rows, items = rows[order], items[order]
        bounds = np.cumsum(np.bincount(rows, minlength=extra))[:-1]
        baskets = [b for b in np.split(items, bounds) if b.size][:queries]
    return db, baskets


def _unique_pairs(rows: np.ndarray, items: np.ndarray, num_items: int):
    """Set semantics: one (row, item) pair per item in a transaction, sorted by row."""
    key = np.unique(rows.astype(np.int64) * num_items + items)
    return key // num_items, key % num_items
