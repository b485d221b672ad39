"""Basket -> top-k queries through the replicated tier: ``Router.submit``.

The serving job (``bench/jobs/serve.py``) with a ``Router`` of the
configuration's replica count in place of the single gateway: one replica a
chip, each a whole gateway over the whole rulebook, behind consistent basket
hashing. The load, the window, the drain and the comparison with the plain
reference are the serving job's. What this adds:

- set-up builds the router with the configuration's ``serving.router``
  settings, and checks that there is a replica a chip and that replica i
  serves from device i;
- the window's counters are the router's (``router_<name>``), the replicas'
  gateway counters summed, and each replica's ``batch_rows_real``
  (``replica<i>_batch_rows_real``), from which ``counters`` gives the busiest
  and the mean replica's rows;
- the check adds ``replicas_unchecked``: the replicas that gave none of the
  checked answers, each answer put down to the ring owner of its basket.
"""

from __future__ import annotations

import time

import numpy as np

from bench import quest
from bench.harness import log
from bench.jobs import common, serve


class Job(serve.Job):
    def setup(self, queries: int | None = None):
        import jax

        from repro.core.streaming import mine_streamed
        from repro.distributed import FaultConfig
        from repro.serving import Router

        serving = self.config["serving"]
        n = serving["replicas"]
        if n != self.cell.chips:
            raise ValueError(f"{n} replicas for a cell of {self.cell.chips} chips")
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
        r = serving["router"]
        self.router = Router(
            rb, n, fault=FaultConfig(max_retries=r["max_retries"], backoff_s=r["backoff_s"]),
            attempt_timeout_s=r["attempt_timeout_s"], vnodes=r["vnodes"],
            supervise=r["supervise"], top_k=serving["top_k"], warmup="ladder")
        self.gateway = _Flat(self.router)
        devices = jax.devices()
        placed = [rep.gateway.devices for rep in self.router.replicas]
        if placed != [{devices[i % len(devices)]} for i in range(n)]:
            raise RuntimeError(f"replica rulebooks live on {placed}, not replica i on device i")
        log(f"set-up parts: generate {t1 - t0} s, write store {t2 - t1} s, mine {t3 - t2} s, "
            f"rulebook {t4 - t3} s ({rb.num_rules} rules), {n} replicas' warm-up "
            f"{time.perf_counter() - t4} s; replica i on device "
            f"{[sorted(d.id for d in p) for p in placed]}")

    def check(self) -> dict:
        from repro.serving.recommend import pack_baskets
        from repro.serving.router import HashRing

        checks = super().check()
        n, r = self.config["serving"]["replicas"], self.config["serving"]["router"]
        pick = [i for i, f in self.kept.items() if i < self.count and self.ok[i]]
        pick = pick[: self.traffic["checked_answers"]]
        ring = HashRing(n, r["vnodes"])
        packed = pack_baskets([self._basket(i) for i in pick], self.quest.items)
        per = np.bincount([ring.owner(row.tobytes()) for row in packed], minlength=n)
        log(f"checked answers per replica {per.tolist()}; in the window "
            f"{self.stats.get('router_failovers')} failovers, "
            f"{self.stats.get('router_attempt_timeouts')} attempt timeouts")
        checks["replicas_unchecked"] = (int(np.sum(per == 0)), 0)
        return checks

    def counters(self) -> dict:
        out = dict(self.stats)
        rows = [v for k, v in out.items() if k.startswith("replica") and k.endswith("_rows_real")]
        if rows:
            out["replica_rows_max"], out["replica_rows_mean"] = max(rows), sum(rows) / len(rows)
        out["window_s"] = self.seconds
        return out


class _Flat:
    """The router as the serving job drives it, with flat numeric stats."""

    def __init__(self, router):
        self.router = router
        self.submit = router.submit

    def stats(self) -> dict:
        s = self.router.stats()
        out = {f"router_{k}": v for k, v in s.items() if isinstance(v, (int, float))}
        for i, rep in enumerate(s["replicas"]):
            gw = rep["gateway"]
            out[f"replica{i}_batch_rows_real"] = gw["batch_rows_real"]
            for k, v in gw.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    out[k] = out.get(k, 0) + v
        return out

    def close(self):
        self.router.close()
