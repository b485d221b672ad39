"""The control: the plain reference put in the program's place, one precision lower.

The configurations state float32 rule scores. The control rounds every rule
score to bfloat16 and sums in float32, as one default pass of a matrix unit
would, and is compared with the float64 reference by the same code and at the
same sizes as a run compares the program. Its readings are the upper ends the
limits in the configuration files are set below.

  python3 bench/control.py --workload serve.t20i6d100k.batch --seeds 1 2 3

prints one JSON line per seed with the numbers a run of that cell compares.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import cells, compare, quest, reference  # noqa: E402


def bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def pack(itemsets: list, num_items: int) -> np.ndarray:
    """Item tuples as uint32 bitsets: bit j of word w is item 32*w + j."""
    out = np.zeros((len(itemsets), (num_items + 31) // 32), np.uint32)
    for r, items in enumerate(itemsets):
        for i in items:
            out[r, i // 32] |= np.uint32(1 << (i % 32))
    return out


def mine_readings(config: dict, seed: int) -> dict:
    q = quest.Quest.from_config(config)
    m = config["mining"]
    db, _ = quest.store_and_queries(q, seed)
    ref = reference.frequent(db, m["min_support"], m["max_k"])
    rules = reference.rules(ref, m["min_confidence"])
    columns = {"ante_packed": pack(rules.ante, q.items), "cons_packed": pack(rules.cons, q.items),
               "ante_len": np.asarray([len(a) for a in rules.ante], np.int32),
               "scores": rules.score.astype(bf16()).astype(np.float32)}
    found = compare.rulebook(columns, rules, m["min_confidence"])
    return {"itemset_mismatches": 0, **found}


def serve_readings(config: dict, seed: int, baskets: int) -> dict:
    q = quest.Quest.from_config(config)
    m, top_k = config["mining"], config["serving"]["top_k"]
    db, queries = quest.store_and_queries(q, seed, baskets)
    rules = reference.rules(reference.frequent(db, m["min_support"], m["max_k"]),
                            m["min_confidence"])
    _, want, acc = reference.recommend(rules, queries, q.items, top_k)
    items, scores, _ = reference.recommend(rules, queries, q.items, top_k,
                                           score_dtype=bf16(), acc_dtype=np.float32)
    return {"unanswered": 0, **compare.answers(items, scores, queries, acc, want,
                                               m["min_confidence"])}


def readings(cell, seed: int) -> dict:
    if cell.traffic["job"] == "mine":
        return mine_readings(cell.config, seed)
    return serve_readings(cell.config, seed, cell.traffic["checked_answers"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = cells.load(args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": cell.name, "seed": seed, **readings(cell, seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
