"""Streaming Map/Reduce mining over an on-disk transaction store.

The paper's jobs never load the DB: each map task streams its HDFS block,
emits partial counts, and a combiner folds them before the reduce. This
module is that dataflow for the miner (DESIGN.md §9): the DB lives in a
``data.store.TransactionStore`` (packed uint32 shards on disk), and each
level's count pass iterates fixed-size row chunks through the SAME jit'd
count step as the in-memory driver, **accumulating per-candidate partial
counts on device** — the combiner. The chunks go to the device as the
store's packed words, for both representations: the dense count step
unpacks them on the device (``kernels.ops.unpack_bits_device``), so the
host reads and places 4·ceil(I/32) bytes a row and unpacks nothing. The
host syncs a candidate pass exactly once, after its last chunk, so per
level there is a single device→host transfer regardless of chunk count.

Host peak RSS is bounded by O(chunk_rows · row_bytes) (plus the candidate
tensors), not the dataset size: chunks are copied out of the mmap'd shards
one at a time, and a ``data.pipeline.ShardedBatchIterator`` double-buffers
the host→device transfer so chunk assembly overlaps device counting.

Exactness: support counting is integer arithmetic and every chunk row is
either a real transaction or an inert zero row (DESIGN.md §3), so the
chunk-sum equals the whole-DB count bit-for-bit — ``mine_streamed`` /
``mine_son_streamed`` are dict-equal to ``mine`` / ``mine_son`` at any
chunk size.

Fault tolerance (DESIGN.md §11):

  * ``mine_streamed(checkpoint=..., resume=True)`` persists the driver's
    complete state through :class:`distributed.checkpoint.MiningCheckpoint`
    — completed levels at every level boundary, plus (every
    ``checkpoint_every_chunks`` chunks) the mid-level pass cursor and the
    in-progress device accumulator. Because the store's chunk iteration is
    step-indexed and deterministic and counting is integer arithmetic,
    folding the remaining chunks into the restored accumulator equals
    folding all chunks into zeros: a resumed mine is dict-identical to an
    uninterrupted one.
  * ``mine_son_streamed(fault=FaultConfig(...))`` dispatches phase-1 shard
    partitions through ``distributed.fault_tolerance.run_partitions`` —
    bounded-retry re-execution plus speculative re-issue of stragglers,
    the paper's Hadoop task-recovery story made real.
"""

from __future__ import annotations

import functools
import math
import time
from typing import TYPE_CHECKING, Callable

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import apriori as ap
from repro.core import son as son_mod
from repro.data.pipeline import ShardedBatchIterator, batch_spec
from repro.distributed.checkpoint import (
    CheckpointMismatch,
    MiningCheckpoint,
    MiningState,
    mining_fingerprint,
    store_fingerprint,
)
from repro.distributed.fault_tolerance import FaultConfig, run_partitions
from repro.obs.mining import phase

if TYPE_CHECKING:  # import-time would cycle: data.store -> core -> streaming
    from repro.data.store import TransactionStore


@functools.lru_cache(maxsize=32)
def make_accum_count_step(mesh, cfg: ap.AprioriConfig) -> Callable:
    """The combiner: jit'd ``(t_chunk, c, lengths, acc) -> acc + counts``.

    Wraps :func:`core.apriori.make_count_step` (so dense/packed, jnp/Pallas
    and the mesh Map/Reduce shape are all inherited unchanged) and folds the
    chunk's counts into a device-resident int32 accumulator — partial
    aggregation happens where the data is, exactly like a Hadoop combiner.
    Cached per (mesh, config): a later mine of the same job reuses the
    compiled step instead of compiling every candidate bucket again.
    """
    count_step = ap.make_count_step(mesh, cfg)

    def step(t_chunk, c_dev, len_dev, acc):
        return acc + count_step(t_chunk, c_dev, len_dev)

    return jax.jit(step)


def _init_acc(kp: int, cfg: ap.AprioriConfig, mesh, init: np.ndarray | None = None):
    arr = np.zeros(kp, dtype=np.int32) if init is None else np.asarray(init, np.int32)
    if mesh is None:
        return jax.numpy.asarray(arr)
    return jax.device_put(arr, NamedSharding(mesh, P(cfg.model_axis)))


def _effective_chunk_rows(chunk_rows: int, cfg: ap.AprioriConfig, mesh) -> int:
    """Round the chunk up to a multiple of the data-shard count so every
    chunk splits evenly over P(data_axes) (padding rows are inert)."""
    if chunk_rows < 1:
        raise ValueError("chunk_rows must be >= 1")
    if mesh is None:
        return chunk_rows
    shards = math.prod(mesh.shape[a] for a in cfg.data_axes)
    return ((chunk_rows + shards - 1) // shards) * shards


def _chunks(store, chunk_rows: int, obs=None, **where):
    """The store's zero-padded chunks of packed uint32 words, as read, for
    either representation (``where``: ``start_chunk`` / ``shards`` of
    ``iter_chunks``); the dense count step unpacks them on the device.

    ``repro.stream.chunk_read`` times each read on the thread that draws the
    chunks. ``repro.stream.chunk_unpack`` times nothing: no unpack is left
    on the host, and the span stays empty only while the benchmark's
    ``chunk_unpack_s.mine`` reader and its span test name it (PERF.md §7)."""
    chunks = store.iter_chunks(chunk_rows, pad=True, **where)
    while True:
        with phase(obs, "stream", "chunk_read"):
            nxt = next(chunks, None)
        if nxt is None:
            return
        with phase(obs, "stream", "chunk_unpack"):
            pass
        yield nxt[0]


def _place_span(obs):
    """The producer's span around each chunk's host→device placement."""
    return functools.partial(phase, obs, "stream", "chunk_place")


def _count_pass_chunks(
    accum_step,
    chunks,
    c_dev,
    len_dev,
    kp,
    cfg,
    mesh,
    prefetch,
    init_acc: np.ndarray | None = None,
    chunks_done: int = 0,
    save_every: int = 0,
    save_fn: Callable | None = None,
    obs=None,
):
    """Fold every DB chunk into a device accumulator; sync ONCE — unless a
    mid-pass checkpoint cadence is set, in which case each save adds exactly
    one extra host sync (the measured checkpoint overhead, DESIGN.md §11).

    ``init_acc``/``chunks_done`` restore an interrupted pass: the caller
    skips the already-folded chunks at the store and hands the saved
    accumulator here; the save cadence stays aligned to ABSOLUTE chunk
    indices so a resumed pass checkpoints at the same points.

    ``obs`` (an :class:`repro.obs.MiningObs`) attributes the pass's time:
    ``prefetch_stall`` is the fold blocking on the chunk iterator,
    ``count_dispatch`` the (async) dispatch of the accumulate step,
    ``host_sync`` the final device→host transfer that also drains the
    device queue, ``checkpoint_write`` the mid-pass saves; the producer
    thread times ``chunk_place``.  The obs-off path is the original
    untouched loop.
    """
    acc = _init_acc(kp, cfg, mesh, init=init_acc)
    done = chunks_done
    it = ShardedBatchIterator(chunks, mesh, batch_spec(cfg.data_axes), prefetch=prefetch,
                              place_span=_place_span(obs))
    try:
        if obs is None:
            for t_chunk in it:
                acc = accum_step(t_chunk, c_dev, len_dev, acc)
                done += 1
                if save_fn is not None and save_every > 0 and done % save_every == 0:
                    save_fn(np.asarray(acc), done)
        else:
            src = iter(it)
            while True:
                t0 = time.perf_counter()
                try:
                    t_chunk = next(src)
                except StopIteration:
                    break
                obs.add_phase("prefetch_stall", t0, time.perf_counter())
                with obs.span("stream", "count_dispatch"):
                    acc = accum_step(t_chunk, c_dev, len_dev, acc)
                obs.on_chunk(int(t_chunk.shape[0]), int(t_chunk.nbytes))
                done += 1
                if save_fn is not None and save_every > 0 and done % save_every == 0:
                    t3 = time.perf_counter()
                    save_fn(np.asarray(acc), done)
                    obs.add_phase("checkpoint_write", t3, time.perf_counter())
    finally:
        it.close()
    with phase(obs, "level", "host_sync"):
        return np.asarray(acc)   # the final host sync of this candidate pass


def count_supports_streamed(
    store: TransactionStore,
    cand_sets: np.ndarray,
    cfg: ap.AprioriConfig = ap.AprioriConfig(),
    mesh=None,
    chunk_rows: int = 8192,
    prefetch: int = 2,
    obs=None,
) -> np.ndarray:
    """Exact support counts of ``cand_sets`` over an on-disk store.

    The streamed twin of the in-memory driver's per-level count: candidates
    split into ``max_candidates_per_pass`` passes padded to the same jit
    buckets; each pass streams all DB chunks through the accumulate step.
    Equals the whole-DB count exactly, for both representations, at any
    ``chunk_rows`` (including sizes that don't divide n — the final chunk
    zero-pads, and zero rows are inert).
    """
    cand_sets = np.asarray(cand_sets, dtype=np.int32)
    num_items = store.num_items
    chunk_rows = _effective_chunk_rows(chunk_rows, cfg, mesh)
    accum_step = make_accum_count_step(mesh, cfg)
    return _count_level_streamed(
        accum_step, store, cand_sets, num_items, cfg, mesh, chunk_rows, prefetch,
        obs=obs,
    )


def _count_level_streamed(
    accum_step,
    store,
    cand_sets,
    num_items,
    cfg,
    mesh,
    chunk_rows,
    prefetch,
    cursor: MiningState | None = None,
    save_cb: Callable | None = None,
    save_every: int = 0,
    obs=None,
):
    """One level's candidate passes over the store.

    ``cursor`` (a mid-level :class:`MiningState`) resumes an interrupted
    level: finished passes' counts are restored verbatim, the in-progress
    pass restarts from its saved accumulator at its saved chunk index, and
    later passes run normally. ``save_cb(counts, pass_start, acc, done)``
    is invoked every ``save_every`` chunks with the level's cursor state.
    """
    k_total = cand_sets.shape[0]
    quantum = ap._candidate_quantum(cfg, mesh)
    counts = np.zeros(k_total, dtype=np.int64)
    start0, resume_chunks, resume_acc = 0, 0, None
    if cursor is not None:
        if cursor.counts is None or cursor.counts.shape[0] != k_total:
            raise CheckpointMismatch(
                f"mid-level checkpoint carries {None if cursor.counts is None else cursor.counts.shape[0]} "
                f"candidate counts, but level {cursor.next_k} regenerated {k_total} "
                "candidates — checkpoint does not match this mine"
            )
        counts[:] = cursor.counts
        start0 = int(cursor.pass_start)
        resume_chunks = int(cursor.chunks_done)
        resume_acc = cursor.acc
    for start in range(start0, k_total, cfg.max_candidates_per_pass):
        chunk_c = cand_sets[start : start + cfg.max_candidates_per_pass]
        kp = ap._pad_bucket(chunk_c.shape[0], quantum)
        if obs is not None:
            obs.observe_max_candidate_bucket(kp)
        with phase(obs, "level", "candidate_place"):
            c_dev, len_dev = ap._place_candidates(chunk_c, kp, num_items, cfg, mesh)
        init_acc, start_chunk = None, 0
        if resume_acc is not None:   # first pass after a mid-level resume only
            if resume_acc.shape[0] != kp:
                raise CheckpointMismatch(
                    f"saved accumulator has {resume_acc.shape[0]} slots, this pass "
                    f"pads to {kp} — candidate bucketing (candidate_pad / mesh) changed"
                )
            init_acc, start_chunk = resume_acc, resume_chunks
            resume_acc = None
        chunks = _chunks(store, chunk_rows, obs, start_chunk=start_chunk)
        if save_cb is not None and save_every > 0:
            def save_fn(acc_np, done, _start=start):
                save_cb(counts, _start, acc_np, done)
        else:
            save_fn = None
        out = _count_pass_chunks(
            accum_step, chunks, c_dev, len_dev, kp, cfg, mesh, prefetch,
            init_acc=init_acc, chunks_done=start_chunk,
            save_every=save_every, save_fn=save_fn, obs=obs,
        )
        counts[start : start + chunk_c.shape[0]] = out[: chunk_c.shape[0]]
    return counts


def _as_manager(checkpoint, store) -> MiningCheckpoint | None:
    if checkpoint is None or checkpoint is False:
        return None
    if isinstance(checkpoint, MiningCheckpoint):
        return checkpoint
    if checkpoint is True:
        return MiningCheckpoint(store.checkpoint_path)
    return MiningCheckpoint(str(checkpoint))


def mine_streamed(
    store: TransactionStore,
    cfg: ap.AprioriConfig = ap.AprioriConfig(),
    mesh=None,
    chunk_rows: int = 8192,
    prefetch: int = 2,
    checkpoint_cb: Callable | None = None,
    resume_state: dict | None = None,
    checkpoint: "MiningCheckpoint | str | bool | None" = None,
    checkpoint_every_chunks: int = 0,
    resume: bool = False,
    obs=None,
) -> ap.AprioriResult:
    """Level-wise Apriori over an on-disk store, dict-equal to ``mine``.

    Identical driver semantics by construction — this is
    ``core.apriori.run_level_loop`` with the count function swapped for the
    chunk-streaming accumulator. Host RSS scales with ``chunk_rows``, not
    ``store.num_transactions``; the DB is re-streamed from disk once per
    candidate pass (sequential mmap reads — the per-pass I/O the paper's
    per-level Hadoop jobs pay too).

    Fault tolerance: pass ``checkpoint=True`` (next to the store manifest,
    ``store.checkpoint_path``), a path, or a :class:`MiningCheckpoint` to
    persist driver state at every level boundary — plus, when
    ``checkpoint_every_chunks > 0``, mid-level at that chunk cadence.
    ``resume=True`` restores the newest committed snapshot (validated
    against the store and config fingerprints) and continues; the result is
    dict-identical to an uninterrupted mine. ``checkpoint_cb`` /
    ``resume_state`` remain the raw level-boundary hooks (in-memory
    restarts, tests) and compose with the manager.
    """
    n, num_items = store.num_transactions, store.num_items
    chunk_rows = _effective_chunk_rows(chunk_rows, cfg, mesh)
    if checkpoint_every_chunks < 0:
        raise ValueError("checkpoint_every_chunks must be >= 0")
    accum_step = make_accum_count_step(mesh, cfg)
    mgr = _as_manager(checkpoint, store)

    if mgr is None:
        if resume:
            raise ValueError("resume=True requires checkpoint=")

        def count_fn(cand_sets, level_k):
            return _count_level_streamed(
                accum_step, store, cand_sets, num_items, cfg, mesh, chunk_rows,
                prefetch, obs=obs,
            )

        return ap.run_level_loop(count_fn, n, num_items, cfg, checkpoint_cb,
                                 resume_state, obs=obs)

    store_fp = store_fingerprint(store)
    mine_fp = mining_fingerprint(cfg, chunk_rows)

    cursor: MiningState | None = None
    if resume:
        loaded = mgr.load_latest()
        if loaded is not None:
            state, manifest = loaded
            mgr.validate(manifest, store_fp, mine_fp)
            resume_state = {"levels": dict(state.levels), "next_k": state.next_k}
            if state.mid_level:
                cursor = state
    else:
        mgr.clear()   # don't mix snapshots of distinct mines under one seq line

    # completed levels as of NOW — what a mid-level snapshot must carry
    done_levels = {"levels": dict(resume_state["levels"]) if resume_state else {}}

    def level_cb(k, levels):
        done_levels["levels"] = dict(levels)
        mgr.save(MiningState(levels=dict(levels), next_k=k + 1), store_fp, mine_fp)
        if checkpoint_cb:
            checkpoint_cb(k, levels)

    def count_fn(cand_sets, level_k):
        nonlocal cursor
        cur, cursor = cursor, None   # the cursor resumes exactly one level
        if cur is not None and cur.next_k != level_k:
            raise CheckpointMismatch(
                f"mid-level checkpoint is for level {cur.next_k}, "
                f"but the loop resumed at level {level_k}"
            )

        def save_cb(counts, pass_start, acc_np, done):
            mgr.save(
                MiningState(
                    levels=done_levels["levels"],
                    next_k=level_k,
                    mid_level=True,
                    pass_start=pass_start,
                    chunks_done=done,
                    counts=counts,
                    acc=acc_np,
                ),
                store_fp,
                mine_fp,
            )

        return _count_level_streamed(
            accum_step, store, cand_sets, num_items, cfg, mesh, chunk_rows, prefetch,
            cursor=cur,
            save_cb=save_cb if checkpoint_every_chunks > 0 else None,
            save_every=checkpoint_every_chunks,
            obs=obs,
        )

    result = ap.run_level_loop(count_fn, n, num_items, cfg, level_cb, resume_state,
                               obs=obs)
    mgr.wait()   # the last boundary snapshot is committed before we return
    return result


def count_union_streamed(
    store: TransactionStore,
    per_level: dict,
    cfg: ap.AprioriConfig = ap.AprioriConfig(),
    mesh=None,
    chunk_rows: int = 8192,
    prefetch: int = 2,
    shards: tuple | None = None,
    obs=None,
) -> dict:
    """Exact streamed counts of a multi-level candidate union in ONE pass
    over the store (or over the shard range ``shards=(s0, s1)``).

    ``per_level`` maps ``k -> (K_k, k) int32`` candidate arrays; the return
    maps ``k -> (K_k,) int64`` counts, aligned. Every level's candidate
    passes are device-placed up front (the union is the modest survivor set,
    not a full level's candidates — this trades the max_candidates_per_pass
    memory bound for a single disk scan), then every DB chunk folds into
    every pass's accumulator. This is SON's phase 2 made reusable: the full
    mine counts the whole union over the whole store, the delta miner
    (DESIGN.md §15) counts the union over appended shards and the novel
    candidates over the base shards — same kernel path, same exactness
    argument (zero-padded rows are inert).
    """
    chunk_rows = _effective_chunk_rows(chunk_rows, cfg, mesh)
    num_items = store.num_items
    accum_step = make_accum_count_step(mesh, cfg)
    quantum = ap._candidate_quantum(cfg, mesh)
    per_level = {
        k: np.asarray(cands, dtype=np.int32)
        for k, cands in sorted(per_level.items())
        if np.asarray(cands).shape[0]
    }
    units = []   # (k, start, rows, c_dev, len_dev, acc)
    for k, cands in per_level.items():
        for start in range(0, cands.shape[0], cfg.max_candidates_per_pass):
            chunk_c = cands[start : start + cfg.max_candidates_per_pass]
            kp = ap._pad_bucket(chunk_c.shape[0], quantum)
            if obs is not None:
                obs.observe_max_candidate_bucket(kp)
            with phase(obs, "level", "candidate_place"):
                c_dev, len_dev = ap._place_candidates(chunk_c, kp, num_items, cfg, mesh)
            units.append([k, start, chunk_c.shape[0], c_dev, len_dev, _init_acc(kp, cfg, mesh)])
    if units:
        chunks = _chunks(store, chunk_rows, obs, shards=shards)
        it = ShardedBatchIterator(chunks, mesh, batch_spec(cfg.data_axes), prefetch=prefetch,
                                  place_span=_place_span(obs))
        try:
            if obs is None:
                for t_chunk in it:
                    for u in units:
                        u[5] = accum_step(t_chunk, u[3], u[4], u[5])
            else:
                src = iter(it)
                while True:
                    t0 = time.perf_counter()
                    try:
                        t_chunk = next(src)
                    except StopIteration:
                        break
                    obs.add_phase("prefetch_stall", t0, time.perf_counter())
                    with obs.span("stream", "count_dispatch"):
                        for u in units:
                            u[5] = accum_step(t_chunk, u[3], u[4], u[5])
                    obs.on_chunk(int(t_chunk.shape[0]), int(t_chunk.nbytes))
        finally:
            it.close()

    counts = {}
    with phase(obs, "level", "host_sync"):
        for k, cands in per_level.items():
            sup = np.zeros(cands.shape[0], dtype=np.int64)
            for uk, start, rows, _, _, acc in units:
                if uk == k:
                    sup[start : start + rows] = np.asarray(acc)[:rows]
            counts[k] = sup
    return counts


def mine_son_streamed(
    store: TransactionStore,
    cfg: ap.AprioriConfig = ap.AprioriConfig(),
    mesh=None,
    chunk_rows: int = 8192,
    prefetch: int = 2,
    fault: FaultConfig | None = None,
    obs=None,
    collect_union: bool = False,
) -> ap.AprioriResult:
    """SON two-phase mining over an on-disk store, dict-equal to
    ``mine_son`` (and to ``mine`` — SON is exact for any partitioning).

    Phase 1 maps over the store's *on-disk shards* as the SON partitions:
    each shard is unpacked and mined locally to completion at the
    shard-scaled threshold. With ``fault=FaultConfig(...)`` the shard
    mappers run through the retrying work queue
    (:func:`distributed.fault_tolerance.run_partitions`): a failed shard
    read or mapper is re-executed with backoff — shards are re-loadable by
    index, the HDFS-split property — stragglers are speculatively
    re-issued, and the executor's :class:`FaultReport` lands on
    ``result.fault_report``. In ``on_exhausted="skip"`` mode a dropped
    partition is an EXPLICITLY reported completeness gap (SON's no-miss
    guarantee needs every partition).

    Phase 2 is ONE streamed exact count of the union — two distributed
    rounds total, never the whole DB in memory.

    ``collect_union=True`` additionally attaches the full PRE-prune union
    with its exact counts as ``result.union_counts`` (``k -> (cands,
    counts)``) — exactly what phase 2 computes and the prune would throw
    away. The incremental count cache (DESIGN.md §15) persists this.
    """
    n = store.num_transactions
    min_count = max(1, math.ceil(cfg.min_support * n))
    chunk_rows = _effective_chunk_rows(chunk_rows, cfg, mesh)

    # ---- phase 1: local mining per on-disk shard, union of local winners --
    report = None
    if fault is None:
        union = son_mod.union_local_winners(
            (store.partition_dense(p) for p in range(store.num_partitions)), cfg
        )
    else:
        def map_shard(p: int) -> dict:
            # re-reads shard p from disk on every (re-)execution — idempotent
            return son_mod.local_winners(store.partition_dense(p), cfg)

        winners, report = run_partitions(map_shard, store.num_partitions, fault,
                                         obs=obs)
        union = son_mod.merge_winners(w for w in winners if w is not None)

    # ---- phase 2: ONE streamed exact count of the whole union ----
    per_level = son_mod.winners_to_arrays(union)
    counts = count_union_streamed(
        store, per_level, cfg, mesh, chunk_rows=chunk_rows, prefetch=prefetch, obs=obs
    )
    levels = {}
    for k, cands in per_level.items():
        sup = counts[k]
        keep = sup >= min_count
        if keep.any():
            levels[k] = (cands[keep], sup[keep])
    return ap.AprioriResult(
        levels=levels, num_transactions=n, min_count=min_count, fault_report=report,
        union_counts=(
            {k: (cands, counts[k]) for k, cands in per_level.items()}
            if collect_union else None
        ),
    )
