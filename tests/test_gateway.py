"""Gateway tests: bit-identity under any arrival interleaving, hot-swap
with zero dropped / zero mixed-generation requests, backpressure reporting,
cache semantics, metrics arithmetic (DESIGN.md §10).

The bit-identity contract: a gateway response equals a direct
``recommend()`` call against the generation named in the response, run at
the same jit bucket (``batch_size=resp.bucket``) — the match contraction is
row-independent, so only the padded batch shape (never the other requests
in the batch) affects a row's floats.
"""

import time
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np
import pytest

from repro.serving import (
    AdmissionRejected,
    BasketCache,
    Gateway,
    GatewayMetrics,
    LatencyHistogram,
    MicroBatcher,
    Request,
    basket_key,
    compile_rulebook,
    pow2_bucket,
    recommend,
)

NUM_ITEMS = 32


@pytest.fixture(scope="module")
def rulebooks(small_db):
    from repro.core.apriori import AprioriConfig, mine

    rb0 = compile_rulebook(
        mine(small_db, AprioriConfig(min_support=0.05, max_k=3, count_impl="jnp")),
        min_confidence=0.3, num_items=NUM_ITEMS,
    )
    rb1 = compile_rulebook(
        mine(small_db, AprioriConfig(min_support=0.12, max_k=3, count_impl="jnp")),
        min_confidence=0.5, num_items=NUM_ITEMS,
    )
    assert rb0.num_rules > rb1.num_rules > 0
    return rb0, rb1


@pytest.fixture(scope="module")
def baskets(small_db):
    return [np.flatnonzero(row).tolist() for row in small_db[:64]]


def check_response(resp, rb, basket, top_k):
    """One response vs the direct batch engine at the answering bucket."""
    direct = recommend(rb, [basket], top_k=top_k, batch_size=resp.bucket)
    assert np.array_equal(resp.items, direct.items[0])
    assert np.array_equal(resp.scores, direct.scores[0])


# ------------------------------------------------------------ bit-identity --
def test_sequential_singles_match_recommend(rulebooks, baskets):
    rb0, _ = rulebooks
    with Gateway(rb0, max_batch=8, max_wait_ms=0.0, cache_capacity=0) as gw:
        for b in baskets[:10]:
            resp = gw.query(b, top_k=5)
            assert resp.generation == 0 and not resp.cached
            check_response(resp, rb0, b, 5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_interleavings_bit_identical(rulebooks, baskets, seed):
    """Arrival-pattern property: singles, concurrent bursts, duplicate
    baskets and mixed top_k all yield responses bit-identical to the batch
    engine for the answering generation."""
    rb0, _ = rulebooks
    rng = np.random.default_rng(seed)
    plan = []                           # (basket index, top_k)
    for _ in range(rng.integers(3, 6)):
        burst = int(rng.integers(1, 24))
        k = int(rng.choice([3, 7]))
        idx = rng.integers(0, len(baskets), burst)
        plan += [(int(i), k) for i in idx]   # duplicates arise naturally

    with Gateway(rb0, max_batch=16, max_wait_ms=1.0, cache_capacity=256) as gw:
        with ThreadPoolExecutor(max_workers=8) as pool:
            futs = list(pool.map(lambda p: (p, gw.submit(baskets[p[0]], top_k=p[1])), plan))
        for (i, k), fut in futs:
            resp = fut.result(timeout=60)
            assert resp.generation == 0
            check_response(resp, rb0, baskets[i], k)


def test_packed_row_submission_equals_id_list(rulebooks, baskets):
    from repro.serving import pack_baskets

    rb0, _ = rulebooks
    with Gateway(rb0, max_batch=4, max_wait_ms=0.0, cache_capacity=0) as gw:
        packed = pack_baskets([baskets[0]], NUM_ITEMS)[0]
        a = gw.query(packed, top_k=5)
        b = gw.query(baskets[0], top_k=5)
        assert np.array_equal(a.items, b.items) and np.array_equal(a.scores, b.scores)


def test_top_k_clamps_to_vocabulary(rulebooks, baskets):
    rb0, _ = rulebooks
    with Gateway(rb0, max_batch=4, max_wait_ms=0.0) as gw:
        resp = gw.query(baskets[0], top_k=10_000)
        assert resp.items.shape == (NUM_ITEMS,)
        check_response(resp, rb0, baskets[0], 10_000)


# ---------------------------------------------------------------- hot-swap --
def test_hot_swap_zero_dropped_zero_mixed(rulebooks, baskets):
    """Concurrent load across a swap: every admitted request resolves, every
    response verifies bit-identically against the generation it names, and
    requests submitted after the swap returns are answered by the new
    generation only."""
    rb0, rb1 = rulebooks
    rbs = {0: rb0, 1: rb1}
    with Gateway(rb0, max_batch=8, max_wait_ms=0.5, queue_depth=4096,
                 cache_capacity=0) as gw:
        pre = [gw.submit(baskets[i % len(baskets)], top_k=5) for i in range(40)]
        for f in pre:                      # guarantee gen-0 traffic completed
            assert f.result(60).generation == 0

        with ThreadPoolExecutor(max_workers=8) as pool:
            mid = list(pool.map(
                lambda i: (i, gw.submit(baskets[i % len(baskets)], top_k=5)),
                range(120)))
            new_gen = gw.hot_swap(rb1)     # swap while the pool is firing
            assert new_gen == 1
        post = [(i, gw.submit(baskets[i % len(baskets)], top_k=5)) for i in range(20)]

        responses = [(i, f.result(timeout=60)) for i, f in mid + post]
        assert len(responses) == 140       # zero dropped
        for i, resp in responses:
            assert resp.generation in (0, 1)
            check_response(resp, rbs[resp.generation], baskets[i % len(baskets)], 5)
        for _, resp in responses[-20:]:    # after hot_swap returned: new gen only
            assert resp.generation == 1
        assert gw.generation == 1
        assert gw.stats()["swaps"] == 1


def test_hot_swap_rejects_vocabulary_change(rulebooks):
    import dataclasses

    rb0, _ = rulebooks
    with Gateway(rb0, max_batch=4, max_wait_ms=0.0) as gw:
        widened = dataclasses.replace(rb0, num_items=NUM_ITEMS * 2)
        with pytest.raises(ValueError, match="vocabulary"):
            gw.hot_swap(widened)


# ------------------------------------------------------------ backpressure --
def test_batcher_backpressure_rejects_are_reported():
    metrics = GatewayMetrics()
    done = []

    def slow_dispatch(group):
        time.sleep(0.05)
        for r in group:
            done.append(r)
            r.future.set_result(r.top_k)

    batcher = MicroBatcher(slow_dispatch, max_batch=2, max_wait_ms=0.0,
                           queue_depth=4, metrics=metrics)
    accepted, rejected = [], 0
    for i in range(30):
        req = Request(packed=np.zeros(1, np.uint32), top_k=i, future=Future(),
                      t_submit=time.perf_counter())
        try:
            batcher.submit(req)
            accepted.append(req)
        except AdmissionRejected as e:
            assert e.reason == "admission queue full"
            rejected += 1
    batcher.close()

    assert rejected > 0                        # overload actually rejected
    assert len(accepted) + rejected == 30      # every request accounted for
    assert metrics.submitted == len(accepted) and metrics.rejected == rejected
    for req in accepted:                       # admitted -> answered, no drops
        assert req.future.result(timeout=10) == req.top_k
    assert len(done) == len(accepted)


def test_gateway_backpressure_counts_are_consistent(rulebooks, baskets):
    rb0, _ = rulebooks
    gw = Gateway(rb0, max_batch=4, max_wait_ms=0.0, queue_depth=2, cache_capacity=0)
    real_launch = gw._launch
    gw._launch = lambda *a, **kw: (time.sleep(0.03), real_launch(*a, **kw))[1]
    futs, rejected = [], 0
    for i in range(60):
        try:
            futs.append(gw.submit(baskets[i % len(baskets)], top_k=5))
        except AdmissionRejected:
            rejected += 1
    responses = [f.result(timeout=60) for f in futs]
    gw.close()
    assert rejected > 0 and len(responses) + rejected == 60
    s = gw.stats()
    assert s["submitted"] == len(responses) and s["rejected"] == rejected
    assert s["completed"] == len(responses) and s["failed"] == 0
    # rejected probes are not misses, and the cache's own counters agree
    # with the gateway metrics even under rejection-heavy load
    assert s["cache_hits"] + s["cache_misses"] == s["submitted"]
    assert s["cache"]["hits"] == s["cache_hits"]
    assert s["cache"]["misses"] == s["cache_misses"]


def test_dispatch_failure_reaches_futures_never_drops():
    metrics = GatewayMetrics()

    def broken_dispatch(group):
        raise ValueError("kernel exploded")

    batcher = MicroBatcher(broken_dispatch, max_batch=4, max_wait_ms=0.0,
                           queue_depth=8, metrics=metrics)
    req = Request(packed=np.zeros(1, np.uint32), top_k=1, future=Future(),
                  t_submit=time.perf_counter())
    batcher.submit(req)
    with pytest.raises(ValueError, match="kernel exploded"):
        req.future.result(timeout=10)
    batcher.close()
    assert metrics.failed == 1


def test_submit_after_close_rejected(rulebooks, baskets):
    rb0, _ = rulebooks
    gw = Gateway(rb0, max_batch=4, max_wait_ms=0.0)
    gw.close()
    with pytest.raises(AdmissionRejected, match="closed"):
        gw.submit(baskets[0])


# ----------------------------------------------------------------- caching --
def test_cache_hit_is_bit_identical_and_generation_scoped(rulebooks, baskets):
    rb0, rb1 = rulebooks
    with Gateway(rb0, max_batch=4, max_wait_ms=0.0, cache_capacity=64) as gw:
        miss = gw.query(baskets[0], top_k=5)
        hit = gw.query(baskets[0], top_k=5)
        assert not miss.cached and hit.cached
        assert np.array_equal(miss.items, hit.items)
        assert np.array_equal(miss.scores, hit.scores)
        assert gw.cache.hits == 1

        other_k = gw.query(baskets[0], top_k=3)          # top_k is in the key
        assert not other_k.cached

        gw.hot_swap(rb1)
        fresh = gw.query(baskets[0], top_k=5)            # generation is in the key
        assert not fresh.cached and fresh.generation == 1
        check_response(fresh, rb1, baskets[0], 5)
        assert gw.cache.hit_rate == gw.metrics.cache_hit_rate
        evicted = gw.cache.evict_generation(0)
        assert evicted > 0


def test_basket_cache_lru_eviction_and_accounting():
    cache = BasketCache(capacity=2)
    k = lambda i: basket_key(np.full(2, i, np.uint32), 5, 0)
    e = lambda i: (np.array([i]), np.array([float(i)]), 0, 1)
    cache.put(k(0), e(0))
    cache.put(k(1), e(1))
    assert cache.get(k(0)) is not None       # refresh 0 -> 1 becomes LRU
    cache.put(k(2), e(2))                    # evicts 1
    assert cache.get(k(1)) is None
    assert cache.get(k(2)) is not None
    snap = cache.snapshot()
    assert snap["size"] == 2 and snap["evictions"] == 1
    assert snap["hits"] == 2 and snap["misses"] == 1
    disabled = BasketCache(capacity=0)
    disabled.put(k(0), e(0))
    assert disabled.get(k(0)) is None and len(disabled) == 0


# ----------------------------------------------------------------- metrics --
def test_latency_histogram_quantiles_conservative():
    h = LatencyHistogram()
    assert h.quantile(0.5) == 0.0
    rng = np.random.default_rng(0)
    samples = rng.uniform(1e-3, 100e-3, 2000)
    for s in samples:
        h.record(float(s))
    for q in (0.5, 0.95, 0.99):
        true = float(np.quantile(samples, q))
        est = h.quantile(q)
        assert est >= true * 0.999           # never an underestimate
        assert est <= true * 1.25 * 1.05     # within one bucket's growth
    snap = h.snapshot()
    assert snap["count"] == 2000
    assert snap["min_ms"] == pytest.approx(samples.min() * 1e3)
    assert snap["max_ms"] == pytest.approx(samples.max() * 1e3)
    assert h.quantile(1.0) <= samples.max() * 1.0001


def test_gateway_metrics_occupancy_and_snapshot():
    m = GatewayMetrics()
    m.record_batch(3, 4)
    m.record_batch(1, 4)
    assert m.batch_occupancy == pytest.approx(0.5)
    m.record_cache(True)
    m.record_cache(False)
    assert m.cache_hit_rate == pytest.approx(0.5)
    m.record_admission(True)
    m.record_response(0.010)
    snap = m.snapshot()
    assert snap["batches"] == 2 and snap["submitted"] == 1
    assert snap["latency"]["count"] == 1
    assert snap["latency"]["p50_ms"] >= 10.0


def test_occupancy_counts_real_vs_padded(rulebooks, baskets):
    rb0, _ = rulebooks
    with Gateway(rb0, max_batch=8, max_wait_ms=0.0, cache_capacity=0) as gw:
        for b in baskets[:5]:
            gw.query(b, top_k=5)
        s = gw.stats()
        assert s["batch_rows_real"] == 5
        assert s["batch_rows_padded"] >= 5
        assert 0.0 < s["batch_occupancy"] <= 1.0
        assert s["latency"]["count"] == 5


WORKER_FIELDS = ("worker_wait_s", "dispatch_launch_s", "dispatch_fetch_s",
                 "dispatch_respond_s")


def test_dispatch_worker_seconds_grow_once_per_dispatch_and_reconcile(rulebooks, baskets):
    """The worker's loop is timed as wait + launch + fetch + respond, each
    folded once per dispatch (the wait once more, for the wait the close
    ends), plus the queue wait summed over each dispatch's requests. They
    appear in stats(), and together they fit in the worker's wall time."""
    rb0, _ = rulebooks
    t0 = time.perf_counter()
    gw = Gateway(rb0, max_batch=8, max_wait_ms=0.0, cache_capacity=0)
    folds = {f: 0 for f in (*WORKER_FIELDS, "queue_wait_s")}
    for f, gauge in gw.metrics._seconds.items():
        def counted(n, _f=f, _inc=gauge.inc):
            folds[_f] += 1
            _inc(n)
        gauge.inc = counted
    t_built = time.perf_counter()
    for b in baskets[:6]:
        gw.query(b, top_k=5)
    futs = [gw.submit(b, top_k=5) for b in baskets[6:30]]
    for f in futs:
        f.result(timeout=60)
    gw.close()
    wall = time.perf_counter() - t0
    s = gw.stats()
    n = s["batches"]
    assert 7 <= n <= 30
    for f in (*WORKER_FIELDS, "queue_wait_s"):
        assert isinstance(s[f], float), f
    assert folds == {"worker_wait_s": n + 1, "dispatch_launch_s": n, "dispatch_fetch_s": n,
                     "dispatch_respond_s": n, "queue_wait_s": n}
    assert all(s[f] > 0.0 for f in WORKER_FIELDS)
    assert 0.0 <= s["queue_wait_s"]
    assert sum(s[f] for f in WORKER_FIELDS) <= wall
    # the worker started after the warm-up: everything it did fits after it
    assert sum(s[f] for f in WORKER_FIELDS[1:]) <= time.perf_counter() - t_built


def test_queue_wait_is_summed_per_request():
    m = GatewayMetrics()
    m.record_batch(3, 4, queue_wait_s=0.006)
    m.record_batch(1, 4, queue_wait_s=0.001)
    snap = m.snapshot()
    assert snap["queue_wait_s"] == pytest.approx(0.007)
    assert snap["batches"] == 2 and snap["batch_rows_real"] == 4
    assert {f: snap[f] for f in WORKER_FIELDS} == dict.fromkeys(WORKER_FIELDS, 0.0)


# ------------------------------------------------------------------ bucket --
def test_pow2_bucket_ladder():
    assert [pow2_bucket(n, 64) for n in (1, 2, 3, 5, 8, 9, 33, 64)] == \
        [1, 2, 4, 8, 8, 16, 64, 64]
    assert pow2_bucket(33, 48) == 48         # non-pow2 max_batch clamps
    assert pow2_bucket(3, 64, multiple=3) == 6
    assert pow2_bucket(1, 64, multiple=4) == 4
    with pytest.raises(ValueError):
        pow2_bucket(0, 64)
    with pytest.raises(ValueError):
        pow2_bucket(65, 64)


# --------------------------------------------------------------- deadlines --
def test_batcher_drops_expired_requests_at_dispatch():
    """A request whose deadline passed while queued is failed with
    DeadlineExceeded at dispatch time — the device never works for a caller
    that has given up — while live requests in the same batch are served."""
    from repro.serving import DeadlineExceeded

    metrics = GatewayMetrics()
    served = []

    def dispatch(group):
        for r in group:
            served.append(r.top_k)
            r.future.set_result(r.top_k)

    batcher = MicroBatcher(dispatch, max_batch=8, max_wait_ms=0.0,
                           queue_depth=16, metrics=metrics)
    now = time.perf_counter()
    expired = Request(packed=np.zeros(1, np.uint32), top_k=1, future=Future(),
                      t_submit=now, deadline=now - 0.001)      # already past
    live = Request(packed=np.zeros(1, np.uint32), top_k=2, future=Future(),
                   t_submit=now, deadline=now + 30.0)
    batcher.submit(expired)
    batcher.submit(live)
    assert live.future.result(timeout=10) == 2
    with pytest.raises(DeadlineExceeded):
        expired.future.result(timeout=10)
    batcher.close()
    assert served == [2]                       # expired never dispatched
    assert metrics.deadline_expired == 1
    assert metrics.failed == 1
    assert metrics.snapshot()["deadline_expired"] == 1


def test_gateway_deadline_ms_bounds_the_request(rulebooks, baskets):
    """deadline_ms=0 expires in the queue (typed failure, counted);
    a generous deadline serves normally and stays bit-identical."""
    from repro.serving import DeadlineExceeded

    rb0, _ = rulebooks
    with Gateway(rb0, max_wait_ms=0.0, warmup=False, cache_capacity=0) as gw:
        gw.query(baskets[0])                   # compile off the clock
        ok = gw.query(baskets[1], deadline_ms=30_000)
        check_response(ok, rb0, baskets[1], gw.default_top_k)
        with pytest.raises(DeadlineExceeded):
            gw.query(baskets[2], deadline_ms=0)
        s = gw.stats()
        assert s["deadline_expired"] == 1
        # deadline expiry is an explicit failure, never a silent drop
        assert s["completed"] == 2 and s["failed"] == 1


# ------------------------------------------------ generation age (§14) -----
def test_generation_age_gauge_resets_on_hot_swap(rulebooks):
    """``generation_age_seconds`` is a LIVE gauge: it grows between reads
    without anyone writing it, and a hot-swap commit re-stamps it — the
    signal the freshness SLO watches."""
    rb0, rb1 = rulebooks
    with Gateway(rb0, max_batch=4, max_wait_ms=0.0, cache_capacity=0) as gw:
        a1 = gw.metrics.generation_age.value
        time.sleep(0.05)
        a2 = gw.metrics.generation_age.value
        assert a2 > a1 >= 0.0                  # ages with no writer
        assert gw.stats()["generation_age_s"] >= a2
        pre_swap = gw.metrics.generation_age.value
        gw.hot_swap(rb1)
        assert gw.metrics.generation_age.value < pre_swap   # re-stamped
        # and it reaches the registry cut the SLO evaluator differences
        cut = gw.metrics.registry.raw_snapshot()
        assert 0.0 <= cut["gateway_generation_age_seconds"] < pre_swap
