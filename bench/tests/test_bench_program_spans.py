"""The program's leaf spans in a profiler capture, and the per-layer metrics
that read their counters.

A small streamed mine and a few gateway dispatches run inside a capture on
the CPU; ``trace.extract`` must find the ``repro.*`` spans as host events
inside the window, and none of them may cover a whole count pass or a whole
dispatch (they name the device's idle gaps, so an enclosing span would hide
their cause). Traced tiny cells then give a value for every per-layer metric
that reads the program's new counters.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from bench import trace
from bench.tests import tiny

MINING = ("repro.level.candidate_gen", "repro.level.candidate_place", "repro.level.host_sync",
          "repro.stream.chunk_read", "repro.stream.chunk_unpack", "repro.stream.chunk_place",
          "repro.stream.count_dispatch")
SERVING = ("repro.batcher.wait", "repro.gateway.launch", "repro.gateway.fetch",
           "repro.gateway.respond")

NEW_METRICS = {
    "mine.tiny": ("chunk_read_s.mine", "chunk_unpack_s.mine", "chunk_place_s.mine",
                  "candidate_place_s.mine", "prefetch_stall_s.mine", "candidate_gen_s.mine"),
    "serve.tiny.batch": ("dispatch_launch_ms.serve_throughput",
                         "dispatch_fetch_ms.serve_throughput",
                         "dispatch_respond_ms.serve_throughput",
                         "worker_wait_share.serve_throughput",
                         "queue_wait_ms.serve_throughput",
                         "batch_occupancy.serve_throughput"),
}


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    from repro.core.apriori import AprioriConfig
    from repro.core.streaming import mine_streamed
    from repro.data.store import ingest_dense
    from repro.obs import MetricsRegistry, MiningObs
    from repro.serving import Gateway, compile_rulebook

    rng = np.random.default_rng(3)
    dense = (rng.random((3000, 48)) < 0.12).astype(np.uint8)
    store = ingest_dense(dense, os.path.join(str(tmp_path_factory.mktemp("spans")), "db"),
                         shard_rows=800)
    cfg = AprioriConfig(min_support=0.02, max_k=3, count_impl="jnp")
    mine_streamed(store, cfg, chunk_rows=512)          # compiles outside the capture
    obs = MiningObs(registry=MetricsRegistry())
    capture = trace.Capture()
    capture.start()
    result = mine_streamed(store, cfg, chunk_rows=512, obs=obs)
    rb = compile_rulebook(result, min_confidence=0.3, num_items=48)
    with Gateway(rb, max_batch=8, max_wait_ms=0.0, cache_capacity=0, warmup="ladder") as gw:
        for row in dense[:6]:
            gw.query(np.flatnonzero(row).tolist(), top_k=5)
        dispatches = gw.stats()["batches"]
    capture.stop()
    return capture.read(), obs.counters(), dispatches


def _events(host, name):
    return sorted((s, s + d, thread) for thread, n, s, d in host if n == name)


def test_program_spans_are_host_events_inside_the_capture(captured):
    events, counters, dispatches = captured
    (w0, w1, _), = _events(events["host"], trace.WINDOW)
    ours = [(n, s, s + d) for _, n, s, d in events["host"] if n.startswith("repro.")]
    assert {n for n, _, _ in ours} >= {*MINING, *SERVING, "repro.rulebook.compile"}
    assert all(w0 <= s <= e <= w1 for _, s, e in ours)
    assert len(_events(events["host"], "repro.stream.count_dispatch")) == \
        counters["mine_chunks_streamed"]
    assert len(_events(events["host"], "repro.gateway.launch")) == dispatches == 6


def test_no_program_span_covers_a_whole_pass_or_dispatch(captured):
    events, _, _ = captured
    host = events["host"]
    dispatch = _events(host, "repro.stream.count_dispatch")
    syncs = _events(host, "repro.level.host_sync")
    passes, first = [], 0
    for s, e, _ in syncs:
        mine = [d for d in dispatch if first <= d[0] < s]
        if mine:
            passes.append((mine[0][0], e))
        first = e
    launches = _events(host, "repro.gateway.launch")
    responds = _events(host, "repro.gateway.respond")
    served = [(a[0], r[1]) for a, r in zip(launches, responds)]
    assert len(passes) == len(syncs) >= 2 and len(served) == len(launches) > 0
    for _, n, s, d in host:
        if n.startswith("repro."):
            for a, b in passes + served:
                assert not (s <= a and b <= s + d), f"{n} covers [{a}, {b}]"


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_traced_tiny_cell_reports_every_program_counter_metric(tmp_path, name):
    root = tiny.make_root(str(tmp_path))
    out = tiny.run(root, name, traced=True)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    for metric in NEW_METRICS[name]:
        assert metric in got, metric
        assert got[metric]["value"] >= 0.0, metric
