"""Store -> compiled rulebook jobs, back to back, as ``launch/mine.py --store ... --rulebook`` runs one.

Set-up generates the seed's rows, writes them through the program's
``StoreWriter`` and runs one whole job, which compiles every count-step shape
the window will use. The window starts jobs until ``seconds`` have passed; the
job in flight at the close completes. ``mine_s`` is the time of all jobs
started in the window over their number. A traced run traces the first job
whole and keeps the program's mining counters on for every job. With a
``count`` (the CPU tests), the window is that many jobs instead of ``seconds``.

The check compares every job's frequent itemsets and supports, and its
compiled rulebook, rule by rule, with the plain reference.
"""

from __future__ import annotations

import time

import numpy as np

from bench import compare, quest, reference, roofline
from bench.harness import annotate, log
from bench.jobs import common


class Job:
    def __init__(self, cell, seed: int, seconds: float, traced: bool, count: int | None = None):
        self.cell, self.seed, self.seconds, self.traced = cell, seed, seconds, traced
        self.limit = count
        self.config = cell.config
        self.jobs = []            # (mine_s, rulebook_s, itemsets, rulebook columns)
        self.obs = None
        self.attempted = self.failed = 0
        self.store_dir = None

    def setup(self):
        t0 = time.perf_counter()
        self.quest = quest.Quest.from_config(self.config)
        self.db, _ = quest.store_and_queries(self.quest, self.seed)
        t1 = time.perf_counter()
        self.store, self.store_dir = common.write_store(self.db)
        self.cfg = common.mining_config(self.config)
        t2 = time.perf_counter()
        self._one(None)           # compiles what the window's jobs run
        log(f"set-up parts: generate {t1 - t0} s, write store {t2 - t1} s, "
            f"warm-up job {time.perf_counter() - t2} s")

    def _one(self, obs):
        from repro.core.streaming import mine_streamed

        t0 = time.perf_counter()
        with annotate("mine", self.traced):
            result = mine_streamed(self.store, self.cfg, obs=obs)
        t1 = time.perf_counter()
        with annotate("rulebook", self.traced):
            rb = common.compile_rulebook(result, self.config)
        t2 = time.perf_counter()
        return t2 - t0, t2 - t1, result, rb

    def window(self, capture):
        if self.traced:
            from repro.obs import MetricsRegistry, MiningObs

            self.obs = MiningObs(registry=MetricsRegistry())
        t_end = time.perf_counter() + self.seconds
        while (time.perf_counter() < t_end if self.limit is None
               else len(self.jobs) < self.limit):
            first = capture is not None and not self.jobs
            if first:
                capture.start()
            total, rb_s, result, rb = self._one(self.obs)
            if first:
                capture.stop()
            columns = {k: np.asarray(getattr(rb, k))
                       for k in ("ante_packed", "cons_packed", "ante_len", "scores")}
            self.jobs.append((total, rb_s, result.as_dict(), columns))
        self.attempted = len(self.jobs)
        log(f"{len(self.jobs)} jobs: " + ", ".join(str(j[0]) for j in self.jobs) + " s")

    def end_to_end(self) -> dict:
        return {"mine_s": sum(j[0] for j in self.jobs) / len(self.jobs)}

    def release(self):
        common.remove(self.store_dir)
        self.store = None

    def check(self) -> dict:
        m, limits = self.config["mining"], self.config["limits"]
        self.ref = reference.frequent(self.db, m["min_support"], m["max_k"])
        rules = reference.rules(self.ref, m["min_confidence"])
        log(f"reference: {len(self.ref.counts)} frequent itemsets, {len(rules.ante)} rules, "
            f"candidates per level {self.ref.candidates}")
        itemsets = sum(compare.itemset_mismatches(j[2], self.ref.counts) for j in self.jobs)
        first = compare.rulebook(self.jobs[0][3], rules, m["min_confidence"])
        found = [first]
        for j in self.jobs[1:]:
            same = all(np.array_equal(j[3][k], self.jobs[0][3][k]) for k in j[3])
            found.append(first if same else compare.rulebook(j[3], rules, m["min_confidence"]))
        return {
            "itemset_mismatches": (itemsets, 0),
            "rule_mismatches": (sum(f["rule_mismatches"] for f in found), 0),
            "rulebook_malformed": (sum(f["rulebook_malformed"] for f in found), 0),
            "rule_score_err": (max(f["rule_score_err"] for f in found), limits["rule_score_err"]),
        }

    def work(self) -> dict:
        n, items = self.quest.transactions, self.quest.items
        ops = nbytes = 0.0
        for k in self.ref.candidates.values():
            o, b = roofline.count_pass(n, k, items)
            ops, nbytes = ops + o, nbytes + b
        return {"count": (ops, nbytes)}

    def layer_values(self) -> dict:
        return {"jobs": len(self.jobs),
                "rulebook_s": sum(j[1] for j in self.jobs) / len(self.jobs)}

    def counters(self) -> dict:
        return self.obs.counters() if self.obs is not None else {}
