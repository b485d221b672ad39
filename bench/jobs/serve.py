"""Basket -> top-k queries into ``Gateway.submit``, open or closed loop.

Set-up generates the seed's rows and query baskets, mines the rows through the
program's streamed store -> rulebook path, and starts a ``Gateway`` over the
rulebook with every batch shape compiled. The window then offers load:

- ``"loop": "open"``: Poisson arrivals at ``rate_rps``, each request timed from
  its due time to its answer; a refused or unanswered request counts as over
  every limit. ``serve_p95_ms`` is the 95th percentile over every request due
  in the window.
- ``"loop": "closed"``: ``callers`` requests kept outstanding; each answer
  releases the next request. ``serve_throughput_rps`` is the answers completed
  in the window over the window.

With a ``count`` (the CPU tests), the window is that many requests instead
of ``seconds``: open-loop requests are all due at its start and nothing waits
on the clock. A traced run traces the last ``trace_seconds`` of the window,
or the whole of a counted one. The check compares a sample of the answered
requests, drawn from the seed before the window, with the plain reference,
and counts the requests that were never answered.
"""

from __future__ import annotations

import functools
import gc
import math
import queue
import time

import numpy as np

from bench import compare, quest, reference, roofline
from bench.harness import log
from bench.jobs import common

ANSWER_WAIT_S = 60.0


def nearest_rank(values: np.ndarray, pct: float) -> float:
    ordered = np.sort(values)
    return float(ordered[max(0, math.ceil(pct / 100 * ordered.size) - 1)])


class Job:
    def __init__(self, cell, seed: int, seconds: float, traced: bool, count: int | None = None):
        self.cell, self.seed, self.seconds, self.traced = cell, seed, seconds, traced
        self.limit = count
        self.config, self.traffic = cell.config, cell.traffic
        self.attempted = self.failed = 0
        self.rate = self.traffic.get("rate_rps")
        self.store_dir = None
        self.gateway = None

    def _arrivals(self, rate: float) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 2])
        n = int(rate * self.seconds + 10 * math.sqrt(rate * self.seconds) + 100)
        t = np.cumsum(rng.exponential(1.0 / rate, n))
        return t[t < self.seconds]

    def planned(self) -> int:
        if self.limit is not None:
            return self.limit
        if self.traffic["loop"] == "open":
            return self._arrivals(self.rate).size
        return int(self.traffic["max_rps"] * self.seconds)

    def setup(self, queries: int | None = None):
        from repro.core.streaming import mine_streamed
        from repro.serving import Gateway

        t0 = time.perf_counter()
        self.quest = quest.Quest.from_config(self.config)
        self.db, self.baskets = quest.store_and_queries(
            self.quest, self.seed, min(queries or self.planned(), self.traffic["basket_pool"]))
        t1 = time.perf_counter()
        store, self.store_dir = common.write_store(self.db)
        t2 = time.perf_counter()
        result = mine_streamed(store, common.mining_config(self.config))
        t3 = time.perf_counter()
        rb = common.compile_rulebook(result, self.config)
        common.remove(self.store_dir)
        t4 = time.perf_counter()
        self.gateway = Gateway(rb, top_k=self.config["serving"]["top_k"], warmup="ladder")
        log(f"set-up parts: generate {t1 - t0} s, write store {t2 - t1} s, mine {t3 - t2} s, "
            f"rulebook {t4 - t3} s ({rb.num_rules} rules), gateway warm-up "
            f"{time.perf_counter() - t4} s")

    # ------------------------------------------------------------- window --
    def window(self, capture, rate: float | None = None):
        """Offer the window's load. Only the futures of a sample drawn from the
        seed are kept; every other request leaves its times in arrays, so the
        harness keeps few objects alive for the collector to scan."""
        n = self.planned() if rate is None else self._arrivals(rate).size
        self.due, self.sent, self.done = (np.full(n, np.nan) for _ in range(3))
        self.ok = np.zeros(n, bool)
        self.refused = np.zeros(n, bool)
        self.completions, self.finished = queue.SimpleQueue(), 0
        rng = np.random.default_rng([self.seed, 3])
        self.kept = dict.fromkeys(rng.permutation(n)[: 8 * self.traffic["checked_answers"]].tolist())
        self.trace_stats = None
        before = self.gateway.stats()
        with GcPauses() as self.gc:
            if self.traffic["loop"] == "open":
                self._open(capture, rate or self.rate)
            else:
                self._closed(capture)
        self.stats = _delta(before, self.gateway.stats())
        self._drain()

    def _basket(self, i):
        """Request i's basket. The pool is cycled; it is far larger than the
        gateway's cache, so a basket never comes back while still cached."""
        return self.baskets[i % len(self.baskets)]

    def _on_done(self, i, completions, future):
        self.done[i] = time.perf_counter()
        self.ok[i] = future.exception() is None
        completions.put(i)

    def _submit(self, i, basket):
        """Submit request i; its answer or refusal is put on ``completions``."""
        from repro.serving.batcher import AdmissionRejected

        self.sent[i] = time.perf_counter()
        try:
            fut = self.gateway.submit(basket)
        except AdmissionRejected:
            self.refused[i] = True
            self.completions.put(i)
            return
        if i in self.kept:
            self.kept[i] = fut
        fut.add_done_callback(functools.partial(self._on_done, i, self.completions))

    def _next_completion(self, timeout: float) -> bool:
        try:
            self.completions.get(timeout=max(0.0, timeout))
        except queue.Empty:
            return False
        self.finished += 1
        return True

    def _trace_at(self, capture, now):
        if capture is not None and self.trace_stats is None and now >= self.t_trace:
            self.trace_stats = self.gateway.stats()
            capture.start()

    def _open(self, capture, rate):
        if self.limit is None:
            times = self._arrivals(rate)[: self.due.size]
            self.t0 = time.perf_counter() + 0.01
            self.t_end = self.t0 + self.seconds
            self.t_trace = self.t_end - self.traffic["trace_seconds"]
        else:
            times = np.zeros(self.limit)
            self.t0 = self.t_trace = time.perf_counter()
        self.due[:] = self.t0 + times
        for i, due in enumerate(self.due.tolist()):
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self._trace_at(capture, due)
            self._submit(i, self._basket(i))
        self._close_trace(capture)
        self.count = times.size
        if self.limit is not None:
            self._close_counted()

    def _closed(self, capture):
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + self.seconds
        self.t_trace = self.t_end - self.traffic["trace_seconds"]
        if self.limit is not None:
            self.t_trace = self.t0
        nxt = 0
        for _ in range(min(self.traffic["callers"], self.due.size)):
            self.due[nxt] = time.perf_counter()
            self._submit(nxt, self._basket(nxt))
            nxt += 1
        while True:
            if not self._next_completion(ANSWER_WAIT_S):
                raise RuntimeError(f"no answer in {ANSWER_WAIT_S} s")
            now = time.perf_counter()
            closed = now >= self.t_end if self.limit is None else nxt == self.limit
            if closed:
                break
            if nxt == self.due.size:
                raise RuntimeError(f"{self.due.size} requests planned ran out before the window "
                                   "closed: raise the mix's max_rps")
            self._trace_at(capture, now)
            self.due[nxt] = now
            self._submit(nxt, self._basket(nxt))
            nxt += 1
        self._close_trace(capture)
        self.count = nxt
        if self.limit is not None:
            self._close_counted()

    def _close_counted(self):
        """A counted window closes once its requests are submitted."""
        self.t_end = time.perf_counter()
        self.seconds = self.t_end - self.t0

    def _close_trace(self, capture):
        if capture is not None and self.trace_stats is not None:
            capture.stop()
            self.trace_stats = _delta(self.trace_stats, self.gateway.stats())

    def _drain(self):
        n = self.count
        deadline = time.perf_counter() + ANSWER_WAIT_S
        while self.finished < n and self._next_completion(deadline - time.perf_counter()):
            pass
        self.unanswered = int(np.sum(np.isnan(self.done[:n]) & ~self.refused[:n]))
        self.answered = np.flatnonzero(self.ok[:n])
        self.attempted = n
        self.failed = n - self.answered.size
        self.latency_ms = np.where(self.ok[:n], self.done[:n] - self.due[:n], np.inf) * 1e3
        lag = (self.sent[:n] - self.due[:n]) * 1e3
        limit = self.traffic.get("latency_limit_ms")
        log(f"{n} requests, {int(self.refused[:n].sum())} refused, {self.unanswered} unanswered, "
            f"{self.failed} failed; latency p50 {nearest_rank(self.latency_ms, 50)} ms, "
            f"p95 {nearest_rank(self.latency_ms, 95)} ms, p99 {nearest_rank(self.latency_ms, 99)} ms"
            + (f", {np.mean(self.latency_ms <= limit)} within {limit} ms" if limit else "")
            + f"; generator lag p50 {nearest_rank(lag, 50)} ms, p99 {nearest_rank(lag, 99)} ms; "
            f"cache hits {self.stats['cache_hits']}, batches {self.stats['batches']}, "
            f"occupancy {self.stats['batch_rows_real'] / max(self.stats['batch_rows_padded'], 1)}; "
            f"{self.gc.summary()}")

    # ------------------------------------------------------------ results --
    def end_to_end(self) -> dict:
        in_window = np.sum(self.done[self.answered] <= self.t_end)
        return {"serve_p95_ms": nearest_rank(self.latency_ms, 95),
                "serve_throughput_rps": float(in_window) / self.seconds}

    def release(self):
        if self.gateway is not None:
            self.gateway.close()
            self.gateway = None

    def check(self) -> dict:
        m, limits = self.config["mining"], self.config["limits"]
        ref = reference.frequent(self.db, m["min_support"], m["max_k"])
        self.rules = reference.rules(ref, m["min_confidence"])
        pick = [i for i, f in self.kept.items() if i < self.count and self.ok[i]]
        pick = pick[: self.traffic["checked_answers"]]
        k = len(pick)
        baskets = [self._basket(i) for i in pick]
        _, ref_scores, ref_acc = reference.recommend(
            self.rules, baskets, self.quest.items, self.config["serving"]["top_k"])
        items = [self.kept[i].result().items for i in pick]
        scores = [self.kept[i].result().scores for i in pick]
        found = compare.answers(items, scores, baskets, ref_acc, ref_scores, m["min_confidence"])
        log(f"reference: {len(self.rules.ante)} rules; {k} answers compared")
        return {
            "unanswered": (int(self.unanswered), 0),
            "bad_answers": (found["bad_answers"], 0),
            "score_gap": (found["score_gap"], limits["score_gap"]),
            "item_gap": (found["item_gap"], limits["item_gap"]),
        }

    def work(self) -> dict:
        if self.trace_stats is None:
            return {}
        return {"rule_match": roofline.rule_match(
            self.trace_stats["batch_rows_real"], len(self.rules.ante), self.quest.items,
            self.trace_stats["batches"])}

    def layer_values(self) -> dict:
        return {}

    def counters(self) -> dict:
        return dict(self.stats)


class GcPauses:
    """The process's garbage-collector pauses while the window runs."""

    def __enter__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))

    def summary(self) -> str:
        full = [d for g, d in self.pauses if g == 2]
        return (f"gc: {len(self.pauses)} collections, {len(full)} full ones taking "
                f"{sum(full) * 1e3} ms in all, longest pause "
                f"{max((d for _, d in self.pauses), default=0.0) * 1e3} ms")


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b
            if isinstance(b[k], (int, float)) and isinstance(a.get(k), (int, float))}
