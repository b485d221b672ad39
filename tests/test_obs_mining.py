"""Mining job counters (obs.mining): provable inertness — obs on/off mined
dicts identical — plus Hadoop-style counter reconciliation, fault-executor
counters, progress reporting, and the serving tier's merged replica
histograms (DESIGN.md §13)."""

import io
import os

import numpy as np
import pytest

from repro.core.apriori import AprioriConfig
from repro.core.streaming import mine_son_streamed, mine_streamed
from repro.data.store import ingest_dense
from repro.distributed.fault_tolerance import (FaultConfig, InjectedFailure,
                                               run_partitions)
from repro.obs import MetricsRegistry, MiningObs, MiningProgress, Tracer

CFG = AprioriConfig(min_support=0.02, max_k=3, count_impl="jnp")


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    rng = np.random.default_rng(11)
    dense = (rng.random((3000, 48)) < 0.12).astype(np.uint8)
    path = os.path.join(str(tmp_path_factory.mktemp("obs_store")), "db")
    return ingest_dense(dense, path, shard_rows=800)


def _assert_same_result(a, b):
    assert set(a.levels) == set(b.levels)
    for k in a.levels:
        assert np.array_equal(a.levels[k][0], b.levels[k][0])
        assert np.array_equal(a.levels[k][1], b.levels[k][1])


def test_mine_streamed_obs_parity_and_counters(store):
    """Instrumentation is observation-only: the mined result is bit-identical
    with obs on/off, and the counters reconcile with the result."""
    plain = mine_streamed(store, CFG, chunk_rows=512)

    reg = MetricsRegistry()
    tracer = Tracer(sample_rate=1.0)
    obs = MiningObs(registry=reg, tracer=tracer)
    inst = mine_streamed(store, CFG, chunk_rows=512, obs=obs)

    _assert_same_result(plain, inst)

    snap = obs.counters()
    total_frequent = sum(v[0].shape[0] for v in plain.levels.values())
    # mine_levels counts levels ATTEMPTED — the final attempt may keep zero
    # itemsets and so not appear in the result dict
    assert len(plain.levels) <= snap["mine_levels"] <= len(plain.levels) + 1
    assert snap["mine_frequent_total"] == total_frequent
    for k, (sets, _) in plain.levels.items():
        assert snap[f'mine_frequent{{level="{k}"}}'] == sets.shape[0]
        assert snap[f'mine_candidates{{level="{k}"}}'] >= sets.shape[0]
    # every level streams the full store once per candidate pass
    assert snap["mine_rows_streamed"] >= store.num_transactions
    assert snap["mine_chunks_streamed"] > 0
    # all five phases of the wall-time split are populated
    for phase in ("candidate_gen", "prefetch_stall", "count_dispatch", "host_sync"):
        assert snap[f'mine_phase_seconds{{phase="{phase}"}}'] > 0.0, phase
    # the trace shows one mine.level root per attempted level, phase children
    roots = [s for s in tracer.spans() if s.name == "mine.level"]
    assert len(roots) == snap["mine_levels"]
    kinds = {s.name for s in tracer.spans()}
    assert {"mine.candidate_gen", "mine.count_dispatch", "mine.prefetch_stall"} <= kinds


def test_mine_son_streamed_obs_parity_and_fault_counters(store):
    fault = FaultConfig(max_workers=2)
    plain = mine_son_streamed(store, CFG, chunk_rows=512, fault=fault)

    obs = MiningObs(registry=MetricsRegistry())
    inst = mine_son_streamed(store, CFG, chunk_rows=512, fault=fault, obs=obs)

    _assert_same_result(plain, inst)
    snap = obs.counters()
    assert snap["mine_partitions_completed"] == store.num_partitions
    assert snap["mine_partition_attempts"] >= store.num_partitions
    assert snap["mine_chunks_streamed"] > 0


def test_fault_executor_counters_mirror_report():
    """Counters track the FaultReport exactly: retries, skips, completions."""
    def worker(p):
        return p * 10

    def injector(p, attempt):
        if p == 1 and attempt == 0:
            raise InjectedFailure("boom")
        if p == 2:                       # always fails -> exhausts -> skipped
            raise InjectedFailure("dead")

    obs = MiningObs(registry=MetricsRegistry())
    fault = FaultConfig(max_retries=1, backoff_s=0.0, speculative=False,
                        on_exhausted="skip", failure_injector=injector)
    results, report = run_partitions(worker, 4, fault, obs=obs)
    assert results == [0, 10, None, 30]

    snap = obs.counters()
    assert snap["mine_partitions_completed"] == report.completed == 3
    assert snap["mine_partition_retries"] == report.retries == 2
    assert snap["mine_partitions_skipped"] == len(report.skipped) == 1
    assert snap["mine_partition_attempts"] == sum(report.attempts.values())
    assert "speculative_wins" in report.to_json()


def test_speculative_win_counter():
    """A straggling partition whose backup copy finishes first shows up in
    both the report and the live counter."""
    import threading

    release = threading.Event()
    calls = {}
    lock = threading.Lock()

    def worker(p):
        with lock:
            calls[p] = calls.get(p, 0) + 1
            nth = calls[p]
        if p == 3 and nth == 1:
            release.wait(timeout=30)     # original copy stalls...
        return p

    obs = MiningObs(registry=MetricsRegistry())
    fault = FaultConfig(max_workers=2, speculative=True, speculative_factor=2.0,
                        backoff_s=0.0)
    try:
        results, report = run_partitions(worker, 4, fault, obs=obs)
    finally:
        release.set()
    assert results == [0, 1, 2, 3]
    snap = obs.counters()
    assert snap["mine_speculative_issued"] == report.speculative_issued
    assert snap["mine_speculative_wins"] == report.speculative_wins
    if report.speculative_issued:        # ...so the backup wins the race
        assert report.speculative_wins >= 1


def test_mining_progress_reporter(store):
    out = io.StringIO()
    progress = MiningProgress(total_rows=store.num_transactions, out=out,
                              interval_s=0.0)
    obs = MiningObs(registry=MetricsRegistry(), progress=progress)
    mine_streamed(store, CFG, chunk_rows=512, obs=obs)
    obs.finish()
    text = out.getvalue()
    assert progress.lines_emitted > 0
    assert "[mine]" in text and "L1" in text
    assert "rows/s" in text


def test_router_stats_aggregate_replica_histograms_by_merge(small_db):
    """The router's latency view is the MERGE of its replicas' histograms —
    total count equals the sum of per-replica counts, no re-measuring."""
    from repro.core.apriori import mine
    from repro.serving import Router, compile_rulebook

    rb = compile_rulebook(
        mine(small_db, AprioriConfig(min_support=0.05, max_k=3, count_impl="jnp")),
        min_confidence=0.3, num_items=32)
    with Router(rb, 2, max_wait_ms=0.2, cache_capacity=0) as router:
        baskets = [list(np.flatnonzero(r)) for r in small_db[:20]]
        for b in baskets:
            router.query(b, timeout=30)
        stats = router.stats()
        merged = stats["replica_latency"]
        per_replica = [r["gateway"]["latency"]["count"] for r in stats["replicas"]]
        assert merged["count"] == sum(per_replica) == len(baskets)
        assert merged["p99_ms"] >= merged["p50_ms"] >= 0.0


@pytest.mark.parametrize("rep", ["dense", "packed"])
def test_every_leaf_phase_counted_once_per_chunk_or_pass(store, rep):
    """Each chunk is read, (dense only) unpacked, placed and dispatched once;
    each candidate pass is placed and synced once; the read that finds the
    store's end adds one chunk_read per pass. The mined dict is identical
    with obs on and off."""
    cfg = AprioriConfig(min_support=0.02, max_k=3, count_impl="jnp", representation=rep)
    plain = mine_streamed(store, cfg, chunk_rows=512)
    tracer = Tracer(sample_rate=1.0)
    obs = MiningObs(registry=MetricsRegistry(), tracer=tracer)
    inst = mine_streamed(store, cfg, chunk_rows=512, obs=obs)
    _assert_same_result(plain, inst)

    snap = obs.counters()
    chunks, levels = snap["mine_chunks_streamed"], snap["mine_levels"]
    passes = chunks // -(-store.num_transactions // 512)
    assert passes >= levels
    n = {}
    for s in tracer.spans():
        n[s.name] = n.get(s.name, 0) + 1
    want = {"mine.chunk_read": chunks + passes, "mine.chunk_place": chunks,
            "mine.count_dispatch": chunks, "mine.prefetch_stall": chunks,
            "mine.candidate_place": passes, "mine.host_sync": passes,
            "mine.candidate_gen": levels,
            "mine.chunk_unpack": chunks if rep == "dense" else 0}
    assert {k: n.get(k, 0) for k in want} == want
    for phase in want:
        if want[phase]:
            assert snap[f'mine_phase_seconds{{phase="{phase[5:]}"}}'] > 0.0, phase
    assert 'mine_phase_seconds{phase="chunk_unpack"}' not in snap or rep == "dense"


def test_son_union_pass_counts_its_leaf_phases(store):
    """The one-scan union count of SON's phase 2 times the same leaves."""
    obs = MiningObs(registry=MetricsRegistry())
    plain = mine_son_streamed(store, CFG, chunk_rows=512)
    _assert_same_result(plain, mine_son_streamed(store, CFG, chunk_rows=512, obs=obs))
    snap = obs.counters()
    for phase in ("chunk_read", "chunk_unpack", "chunk_place", "count_dispatch",
                  "candidate_place", "host_sync", "prefetch_stall"):
        assert snap[f'mine_phase_seconds{{phase="{phase}"}}'] > 0.0, phase
