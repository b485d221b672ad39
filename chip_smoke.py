#!/usr/bin/env python3
"""Run ingest -> mine -> rulebook -> serve on a TPU, and check every result.

  python chip_smoke.py             # one chip, the whole main path
  python chip_smoke.py --chips 4   # 2x2-mesh mine + four one-chip replicas

The deployment is IBM Quest T10I4D100K (Agrawal & Srikant 1994, Table 3):
|D| = 100,000 transactions over N = 1,000 items, average basket |T| = 10,
average pattern |I| = 4, |L| = 2,000 patterns, generated from seed 0 by
``data/synthetic.py``, mined at min-support 0.01 up to 6-itemsets.

One chip: the store is ingested (``data.store.ingest_quest``) and mined with
the streamed driver, as ``launch/mine.py --store`` does, with ``impl="auto"``
in both representations (dense: the MXU kernel; packed: the VPU kernel).
The two results must be dict-identical, equal the ``kernels/ref.py`` oracles
run on the same chip, have the published per-level counts, and agree with a
NumPy count over the unpacked store for a sample of frequent and rejected
candidates at each level. The rulebook is compiled and served through
``Gateway`` as ``launch/serve.py`` does, and a sample of responses is
compared with ``recommend_python`` (float64 NumPy).

``--chips 4``: the same store mined on a 2x2 ("data", "model") mesh in both
representations must equal the one-chip mine of this process, and a
``Router`` over four replicas must serve each replica from its own chip,
with answers that agree with ``recommend_python``.

Everything runs in this one process, which holds the chip(s). The last line
of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

QUEST = dict(num_transactions=100_000, num_items=1000, avg_len=10,
             avg_pattern_len=4, num_patterns=2000, seed=0)
MIN_SUPPORT, MAX_K = 0.01, 6
EXPECTED_LEVELS = {1: 108, 2: 584, 3: 1159, 4: 1084, 5: 464, 6: 74}
CHUNK_ROWS = SHARD_ROWS = 8192      # launch/mine.py's streaming defaults
SPOT_CHECKS = 16                    # per level, frequent and rejected each
MIN_CONFIDENCE, TOP_K, MAX_BATCH = 0.4, 10, 64
BASKETS, REQUESTS, CONCURRENCY = 2048, 4096, 16
CHECKED_RESPONSES = 300
# f32 rounding of a sum of rule scores: the kernel accumulates in f32, the
# reference in f64. A reduced-precision (bf16) pass would be off by ~1e-3.
SCORE_RTOL, SCORE_ATOL = 1e-5, 1e-6


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileCounter:
    """Counts XLA programs compiled (or fetched from the persistent cache)
    and persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phases:
    def __init__(self, counter: CompileCounter):
        self.counter = counter
        self.rows = []

    def run(self, name, fn, *args, **kw):
        c0 = self.counter.compiles
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        dt = time.perf_counter() - t0
        n = self.counter.compiles - c0
        self.rows.append((name, dt, n))
        log(f"phase {name}: {dt} s, {n} programs compiled")
        return out


def require_tpu(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(f"no TPU found: JAX's devices are {devices[0].platform}")
    check(len(devices) >= chips, f"--chips {chips} needs {chips} TPU devices, JAX sees {len(devices)}")
    return devices


def mine_cfg(representation: str, impl: str = "auto", mesh=None):
    from repro.core.apriori import AprioriConfig

    axes = dict(data_axes=("data",), model_axis="model") if mesh is not None else {}
    return AprioriConfig(min_support=MIN_SUPPORT, max_k=MAX_K, count_impl=impl,
                         representation=representation, **axes)


def level_counts(res) -> dict:
    return {k: int(v[0].shape[0]) for k, v in sorted(res.levels.items())}


def check_pallas_lowering(num_items: int, mesh=None) -> None:
    """The count step that runs is the Pallas kernel: its program holds a
    ``tpu_custom_call`` (and, on a mesh, the all-reduce of the counts)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.itemsets import packed_words
    from repro.core.streaming import make_accum_count_step
    from repro.kernels.ops import resolve_impl

    check(resolve_impl("auto") == "pallas", f"impl='auto' resolves to {resolve_impl('auto')!r}")
    kp = 1024
    for rep in ("dense", "packed"):
        cfg = mine_cfg(rep, mesh=mesh)
        # the streamed chunk is the store's words for both representations;
        # a candidate is int8 items (dense) or words (packed)
        c_width, c_dtype = ((num_items, jnp.int8) if rep == "dense"
                            else (packed_words(num_items), jnp.uint32))
        shard = {}
        if mesh is not None:
            shard = dict(t=NamedSharding(mesh, P(("data",), None)),
                         c=NamedSharding(mesh, P("model", None)),
                         v=NamedSharding(mesh, P("model")))
        sds = lambda shape, dt, s: jax.ShapeDtypeStruct(shape, dt, sharding=shard.get(s))
        lowered = make_accum_count_step(mesh, cfg).lower(
            sds((CHUNK_ROWS, packed_words(num_items)), jnp.uint32, "t"),
            sds((kp, c_width), c_dtype, "c"),
            sds((kp,), jnp.int32, "v"), sds((kp,), jnp.int32, "v"))
        text = lowered.as_text()
        check("tpu_custom_call" in text, f"{rep} count step has no Pallas kernel")
        if mesh is not None:
            hlo = lowered.compile().as_text()
            check("all-reduce" in hlo, f"{rep} mesh count step has no all-reduce")
    log("count steps lower to the Pallas kernel (tpu_custom_call)"
        + (" with an all-reduce over the mesh" if mesh is not None else ""))


def spot_check(store, res) -> int:
    """Supports of sampled frequent and rejected candidates, counted with
    NumPy over the unpacked store (no JAX)."""
    from repro.core.candidates import generate_candidates
    from repro.core.itemsets import singleton_itemsets

    dense = store.read_dense().astype(bool)
    rng = np.random.default_rng(0)
    passed = 0
    for k in sorted(res.levels):
        sets, sup = res.levels[k]
        cands = singleton_itemsets(store.num_items) if k == 1 else generate_candidates(res.levels[k - 1][0])
        freq = {tuple(r) for r in sets.tolist()}
        rejected = [c for c in cands.tolist() if tuple(c) not in freq]
        pick_f = rng.choice(len(sets), size=min(SPOT_CHECKS, len(sets)), replace=False)
        pick_r = rng.choice(len(rejected), size=min(SPOT_CHECKS, len(rejected)), replace=False)
        for i in pick_f:
            got = int(dense[:, sets[i]].all(axis=1).sum())
            check(got == int(sup[i]) and got >= res.min_count,
                  f"level {k}: {sets[i].tolist()} mined {int(sup[i])}, NumPy counts {got}")
            passed += 1
        for i in pick_r:
            got = int(dense[:, rejected[i]].all(axis=1).sum())
            check(got < res.min_count,
                  f"level {k}: rejected {rejected[i]} has NumPy support {got} >= {res.min_count}")
            passed += 1
    return passed


def mine_both(phases, store, mesh=None, tag="") -> dict:
    """Streamed mine in both representations, first call (with compiles) and
    steady call; the results must be dict-identical."""
    from repro.core.streaming import mine_streamed

    out = {}
    for rep in ("dense", "packed"):
        cfg = mine_cfg(rep, mesh=mesh)
        first = phases.run(f"{tag}mine_{rep}_first", mine_streamed, store, cfg,
                           mesh=mesh, chunk_rows=CHUNK_ROWS)
        steady = phases.run(f"{tag}mine_{rep}_steady", mine_streamed, store, cfg,
                            mesh=mesh, chunk_rows=CHUNK_ROWS)
        check(first.as_dict() == steady.as_dict(), f"{tag}{rep}: repeated mine differs")
        out[rep] = steady
    check(out["dense"].as_dict() == out["packed"].as_dict(),
          f"{tag}dense and packed mines differ")
    return out


def serve(srv, baskets: np.ndarray):
    """Closed-loop clients, as launch/serve.py: request i asks for basket
    i mod len(baskets). Returns the responses in request order."""
    responses = [None] * REQUESTS
    errors = []
    lock = threading.Lock()

    def client(indices):
        for i in indices:
            try:
                responses[i] = srv.submit(baskets[i % len(baskets)]).result(timeout=120)
            except Exception as e:  # noqa: BLE001 — every failure is reported below
                with lock:
                    errors.append(f"request {i}: {type(e).__name__}: {e}")

    with ThreadPoolExecutor(max_workers=CONCURRENCY) as pool:
        for f in [pool.submit(client, range(w, REQUESTS, CONCURRENCY)) for w in range(CONCURRENCY)]:
            f.result()
    check(not errors, f"{len(errors)} requests failed, first: {errors[:1]}")
    return responses


def check_responses(rb, baskets: np.ndarray, responses) -> tuple[int, int]:
    """Compare sampled responses with ``recommend_python`` (float64).

    Scores must agree to f32 rounding. Item lists must be equal, except that
    a slot may hold another item whose reference score ties the reference's
    item in that slot within f32 rounding (lax.top_k and the reference break
    such ties differently). Returns (identical lists, lists differing only
    in tied slots)."""
    from repro.serving.recommend import recommend_python, rulebook_as_python

    rng = np.random.default_rng(1)
    picks = rng.choice(len(responses), size=min(CHECKED_RESPONSES, len(responses)), replace=False)
    sample = baskets[picks % len(baskets)]
    ref = recommend_python(rb, sample, top_k=rb.num_items, decoded=rulebook_as_python(rb))
    exact = tied = 0
    for j, i in enumerate(picks):
        resp = responses[i]
        k = resp.items.shape[0]
        want_items, want_scores = ref.items[j], ref.scores[j].astype(np.float64)
        got_scores = resp.scores.astype(np.float64)
        check(np.allclose(got_scores, want_scores[:k], rtol=SCORE_RTOL, atol=SCORE_ATOL),
              f"request {i}: scores {got_scores.tolist()} vs reference {want_scores[:k].tolist()}")
        if np.array_equal(resp.items, want_items[:k]):
            exact += 1
            continue
        score_of = np.empty(rb.num_items)
        score_of[want_items] = want_scores
        check(len(set(resp.items.tolist())) == k
              and np.allclose(score_of[resp.items], want_scores[:k], rtol=SCORE_RTOL, atol=SCORE_ATOL),
              f"request {i}: items {resp.items.tolist()} vs reference {want_items[:k].tolist()}")
        tied += 1
    return exact, tied


def gateway_kw():
    return dict(impl="auto", top_k=TOP_K, max_batch=MAX_BATCH, max_wait_ms=1.0,
                queue_depth=1024, cache_capacity=4096, warmup="ladder")


def one_chip(phases, store) -> None:
    from repro.core.streaming import mine_streamed
    from repro.serving import Gateway, compile_rulebook

    check_pallas_lowering(store.num_items)
    mined = mine_both(phases, store)
    res = mined["packed"]
    levels = level_counts(res)
    log(f"levels {levels}, total {res.total_frequent}, min_count {res.min_count}")
    check(levels == EXPECTED_LEVELS, f"levels {levels} != expected {EXPECTED_LEVELS}")
    for rep in ("dense", "packed"):
        oracle = phases.run(f"oracle_jnp_{rep}", mine_streamed, store,
                            mine_cfg(rep, impl="jnp"), chunk_rows=CHUNK_ROWS)
        check(oracle.as_dict() == res.as_dict(), f"Pallas mine differs from the {rep} jnp oracle")
    log("dense == packed == jnp oracles (dict-identical)")
    spots = phases.run("numpy_spot_check", spot_check, store, res)
    log(f"NumPy spot checks passed: {spots}")

    rb = phases.run("rulebook", compile_rulebook, res, min_confidence=MIN_CONFIDENCE,
                    num_items=store.num_items)
    log(f"rulebook: {rb.num_rules} rules ({rb.num_rows} padded rows)")
    check(rb.num_rules > 0, "empty rulebook")
    baskets = next(store.iter_chunks(BASKETS))[0]
    gw = phases.run("gateway_warmup", Gateway, rb, **gateway_kw())
    with gw:
        responses = phases.run("serve", serve, gw, baskets)
        stats = gw.stats()
    wall = phases.rows[-1][1]
    log(f"served {len(responses)} responses in {wall} s, {phases.rows[-1][2]} programs "
        f"compiled after warmup, cache hit rate {stats['cache_hit_rate']}, "
        f"batch occupancy {stats['batch_occupancy']}")
    exact, tied = check_responses(rb, baskets, responses)
    log(f"reference checks passed: {exact + tied} sampled responses "
        f"({exact} identical item lists, {tied} differing only in tied slots)")


def four_chips(phases, store, devices) -> None:
    from repro.core.streaming import mine_streamed
    from repro.launch.mesh import make_auto_mesh
    from repro.serving import Router, compile_rulebook

    mesh = make_auto_mesh((2, 2), ("data", "model"))
    check_pallas_lowering(store.num_items, mesh=mesh)
    single = phases.run("mine_packed_one_chip", mine_streamed, store, mine_cfg("packed"),
                        chunk_rows=CHUNK_ROWS)
    check(level_counts(single) == EXPECTED_LEVELS, f"one-chip levels {level_counts(single)}")
    meshed = mine_both(phases, store, mesh=mesh, tag="mesh2x2_")
    for rep, res in meshed.items():
        check(res.as_dict() == single.as_dict(), f"2x2 {rep} mine differs from the one-chip mine")
    log(f"2x2 mesh mine (dense, packed) == one-chip mine: levels {level_counts(single)}")

    rb = compile_rulebook(single, min_confidence=MIN_CONFIDENCE, num_items=store.num_items)
    baskets = next(store.iter_chunks(BASKETS))[0]
    router = phases.run("router_warmup", Router, rb, 4, **gateway_kw())
    with router:
        placed = [rep.gateway.devices for rep in router.replicas]
        check(placed == [{d} for d in devices[:4]],
              f"replica rulebooks live on {placed}, not one chip each")
        log(f"replica i serves from chip i: {[sorted(d.id for d in p) for p in placed]}")
        responses = phases.run("router_serve", serve, router, baskets)
        stats = router.stats()
    served = [r["gateway"]["batch_rows_real"] for r in stats["replicas"]]
    log(f"rows served per replica: {served}")
    check(all(n > 0 for n in served), "a replica served nothing")
    exact, tied = check_responses(rb, baskets, responses)
    log(f"reference checks passed: {exact + tied} sampled responses "
        f"({exact} identical item lists, {tied} differing only in tied slots)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    try:
        devices = require_tpu(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    import jax

    log(f"devices: {len(devices)} x {devices[0].device_kind}; jax {jax.__version__}; "
        f"compile cache {cache_dir}")
    from repro.data.store import ingest_quest
    from repro.data.synthetic import QuestConfig

    phases = Phases(counter)
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_store_") as store_dir:
            store = phases.run("ingest", ingest_quest, QuestConfig(**QUEST), store_dir,
                               shard_rows=SHARD_ROWS, chunk_rows=CHUNK_ROWS)
            log(f"store: n={store.num_transactions} items={store.num_items} "
                f"shards={store.num_partitions}")
            if args.chips == 1:
                one_chip(phases, store)
            else:
                four_chips(phases, store, devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"programs compiled: {counter.compiles}, persistent-cache hits: {counter.cache_hits}")
    log("phases (one run, not a benchmark): "
        + json.dumps({name: [dt, n] for name, dt, n in phases.rows}))
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
