"""Distributed level-wise Apriori — the paper's algorithm (§3.3) on a TPU mesh.

Per level k:
  driver (host):  candidate generation from F_{k-1}   (core.candidates)
  Map (device):   local support counting per transaction shard
                  (kernels.support_count — the MXU containment matmul)
  Reduce:         lax.psum of the count vector over the data axes
  driver (host):  prune by min support -> F_k

The candidate axis is additionally sharded over the 'model' mesh axis, a 2-D
decomposition of the paper's 1-D map phase (DESIGN.md §5). Padding rules:
transactions pad with zero rows (inert), candidates pad with |c| = -1 rows
(never match). Counting is exact (int32).

Two device representations of the transaction store (DESIGN.md §4):
  * ``dense``  — {0,1} int8 (N, I); counting is the MXU containment matmul.
  * ``packed`` — uint32 bitsets (N, ceil(I/32)); counting is the VPU
    bitwise-AND containment kernel, 8–32× less HBM traffic per cell.
The DB is packed + device-placed ONCE (``place_db``), and a level's
candidate passes run as a depth-2 pipeline — the host packs and dispatches
pass p+1 while the device counts pass p, blocking on a pass only after its
successor is in flight (and on the last one when the prune needs the
values).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import candidates as cand_mod
from repro.core import itemsets as enc
from repro.core.mapreduce import MapReduceJob, mapreduce, pad_rows_to_shards
from repro.kernels import ops as kops
from repro.obs.mining import phase


@dataclasses.dataclass(frozen=True)
class AprioriConfig:
    min_support: float = 0.01          # fraction of |DB|; min_count = ceil(frac * N)
    max_k: int = 8                     # maximum itemset size to mine
    count_impl: str = "auto"           # auto | jnp | pallas | pallas_interpret
    representation: str = "dense"      # dense {0,1} int8 | packed uint32 bitsets
    data_axes: tuple = ("data",)       # mesh axes sharding the transaction rows
    model_axis: str | None = None      # mesh axis sharding the candidate rows
    candidate_pad: int = 256           # K padded to a multiple (jit bucket + divisibility)
    max_candidates_per_pass: int = 1 << 16  # split huge candidate sets across passes
    use_naive_paper_map: bool = False  # paper's 'all subsets' enumeration (small I only)
    operand_dtype: str = "bf16"        # dense kernel operand mode (bf16 MXU / int8)
    packed_mode: str = "and_cmp"       # packed kernel containment mode (| popcount)


@dataclasses.dataclass
class AprioriResult:
    """k -> (itemsets (F_k, k) int32, supports (F_k,) int64).

    ``fault_report`` is populated only by the fault-tolerant SON executor
    (``streaming.mine_son_streamed(fault=...)``): what the retrying work
    queue actually did — retries, speculative copies, skipped partitions.
    """

    levels: dict
    num_transactions: int
    min_count: int
    fault_report: object | None = dataclasses.field(default=None, compare=False)
    # the full pre-prune SON phase-2 union with exact counts, k -> (cands,
    # counts) — populated only by mine_son_streamed(collect_union=True); the
    # raw material of the incremental count cache (DESIGN.md §15)
    union_counts: dict | None = dataclasses.field(default=None, compare=False)

    def frequent(self, k: int) -> np.ndarray:
        return self.levels[k][0] if k in self.levels else np.zeros((0, k), np.int32)

    def support(self, itemset) -> int:
        k = len(itemset)
        if k not in self.levels:
            return 0
        sets, sup = self.levels[k]
        hit = np.all(sets == np.asarray(sorted(itemset), np.int32)[None, :], axis=1)
        idx = np.flatnonzero(hit)
        return int(sup[idx[0]]) if idx.size else 0

    def as_dict(self) -> dict:
        out = {}
        for k, (sets, sup) in self.levels.items():
            for row, s in zip(sets, sup):
                out[tuple(int(x) for x in row)] = int(s)
        return out

    @property
    def total_frequent(self) -> int:
        return sum(v[0].shape[0] for v in self.levels.values())


def _pad_bucket(k: int, quantum: int) -> int:
    """Pad K to a power-of-two-ish bucket (bounds jit recompiles to O(log K))."""
    k = max(k, 1)
    bucket = quantum
    while bucket < k:
        bucket *= 2
    return bucket


def make_count_step(
    mesh: jax.sharding.Mesh | None,
    cfg: AprioriConfig,
) -> Callable:
    """Build the jit'd Map/Reduce support-count step.

    Dense:  fn(T (N,I) int8,  C (Kp,I) int8,  lengths (Kp,) int32)
    Packed: fn(T (N,W) uint32, C (Kp,W) uint32, lengths (Kp,) int32)
    with T sharded over data_axes -> counts (Kp,) int32, replicated over the
    data axes, sharded over model_axis. The sharded path is identical for
    both representations — P(data_axes, None) over rows, whatever the row
    payload is (DESIGN.md §2). Its ops carry the name scope
    ``repro.count_step``, whatever implements the count.
    """
    if cfg.representation == "packed":

        def local_count(t, c, ln):
            with jax.named_scope("repro.count_step"):
                return kops.support_count_packed(
                    t, c, ln, impl=cfg.count_impl, mode=cfg.packed_mode
                )

    elif cfg.representation == "dense":

        def local_count(t, c, ln):
            with jax.named_scope("repro.count_step"):
                return kops.support_count(
                    t, c, ln, impl=cfg.count_impl, operand_dtype=cfg.operand_dtype
                )

    else:
        raise ValueError(f"representation must be dense|packed, got {cfg.representation!r}")

    if mesh is None or math.prod(mesh.shape.values()) == 1:
        return jax.jit(local_count)

    job = MapReduceJob(map_fn=local_count, reduce_axes=tuple(cfg.data_axes))
    in_specs = (
        P(cfg.data_axes, None),          # transactions: HDFS-block row partition
        P(cfg.model_axis, None),         # candidates: 2-D decomposition over 'model'
        P(cfg.model_axis),
    )
    return mapreduce(job, mesh, in_specs=in_specs, out_specs=P(cfg.model_axis))


def place_db(t_np: np.ndarray, cfg: AprioriConfig, mesh) -> jax.Array:
    """Encode + device-place the transaction store ONCE for the whole mine.

    Packs to uint32 bitsets when ``cfg.representation == "packed"``, pads
    rows to the data-shard count (zero rows are inert for both
    representations), and row-shards over the data axes — the HDFS block
    layout of the paper, P(data_axes, None) regardless of row payload.
    """
    store = enc.pack_bits(t_np) if cfg.representation == "packed" else t_np
    if mesh is None:
        return jnp.asarray(store)
    data_shards = math.prod(mesh.shape[a] for a in cfg.data_axes)
    t_pad, _ = pad_rows_to_shards(store, data_shards)
    return jax.device_put(t_pad, NamedSharding(mesh, P(cfg.data_axes, None)))


def _candidate_quantum(cfg: AprioriConfig, mesh) -> int:
    """Pad quantum for the candidate axis: at least ``candidate_pad``, and a
    multiple of the model-shard count so every bucket splits evenly over
    P(model_axis) (``_pad_bucket`` only doubles, which preserves the
    divisibility — e.g. 3 shards with pad 256 give buckets 258, 516, ...)."""
    model_shards = mesh.shape[cfg.model_axis] if (mesh is not None and cfg.model_axis) else 1
    quantum = max(cfg.candidate_pad, model_shards)
    return ((quantum + model_shards - 1) // model_shards) * model_shards


def _place_candidates(chunk: np.ndarray, kp: int, num_items: int, cfg: AprioriConfig, mesh):
    """Encode one candidate pass to its device tensors: (Kp, ·) itemset rows
    (dense int8 or packed uint32) zero-padded to the bucket, plus the
    lengths vector with |c| = -1 padding sentinels, sharded P(model_axis)
    when a mesh is given. Shared by the in-memory and streaming drivers."""
    if cfg.representation == "packed":
        c_host = np.zeros((kp, enc.packed_words(num_items)), dtype=np.uint32)
        c_host[: chunk.shape[0]] = enc.itemsets_to_packed(chunk, num_items)
    else:
        c_host = np.zeros((kp, num_items), dtype=np.int8)
        c_host[: chunk.shape[0]] = enc.itemsets_to_dense(chunk, num_items)
    lengths = np.full(kp, -1, dtype=np.int32)
    lengths[: chunk.shape[0]] = chunk.shape[1]
    if mesh is not None:
        c_dev = jax.device_put(c_host, NamedSharding(mesh, P(cfg.model_axis, None)))
        len_dev = jax.device_put(lengths, NamedSharding(mesh, P(cfg.model_axis)))
    else:
        c_dev, len_dev = jnp.asarray(c_host), jnp.asarray(lengths)
    return c_dev, len_dev


def _count_level(count_step, t_dev, cand_sets: np.ndarray, num_items: int, cfg: AprioriConfig, mesh):
    """Count supports for one level's candidates, in passes, padded/bucketed.

    Passes form a depth-2 pipeline: the host builds and device-places the
    candidate tensors for pass p+1 while the device counts pass p, and only
    syncs a pass once its successor is dispatched (the last sync happens when
    the caller's prune needs the values, DESIGN.md §5). The bounded depth
    keeps at most two passes of candidate tensors live on device, preserving
    the memory bound ``max_candidates_per_pass`` exists to provide.
    """
    k_total = cand_sets.shape[0]
    quantum = _candidate_quantum(cfg, mesh)
    counts = np.zeros(k_total, dtype=np.int64)
    pending = []

    def _drain(limit):
        while len(pending) > limit:
            start, m, out = pending.pop(0)
            counts[start : start + m] = np.asarray(out)[:m]

    for start in range(0, k_total, cfg.max_candidates_per_pass):
        chunk = cand_sets[start : start + cfg.max_candidates_per_pass]
        kp = _pad_bucket(chunk.shape[0], quantum)
        c_dev, len_dev = _place_candidates(chunk, kp, num_items, cfg, mesh)
        pending.append((start, chunk.shape[0], count_step(t_dev, c_dev, len_dev)))
        _drain(limit=1)   # sync pass p only once pass p+1 is in flight
    _drain(limit=0)
    return counts


def run_level_loop(
    count_fn: Callable[[np.ndarray], np.ndarray],
    n: int,
    num_items: int,
    cfg: AprioriConfig,
    checkpoint_cb: Callable | None = None,
    resume_state: dict | None = None,
    obs=None,
) -> AprioriResult:
    """The driver's level loop, abstracted over HOW candidates are counted.

    ``count_fn(cand_sets (K, k) int32, level_k) -> supports (K,) int``. The
    level index lets a counting backend carry per-level resume state (the
    streamed driver's mid-level chunk cursor, DESIGN.md §11). Candidate
    generation, min-support pruning, checkpointing and termination live
    here — ``mine`` (whole DB device-resident) and
    ``core.streaming.mine_streamed`` (per-level chunk streaming over an
    on-disk store) both instantiate it, so the two drivers cannot drift.

    Determinism contract: given the same DB and config, the candidate array
    passed to ``count_fn`` for level k is a pure function of F_{k-1}
    (``generate_candidates`` is np.unique-canonical) — which is what lets a
    resumed mine regenerate the in-progress level's candidates instead of
    persisting them.

    ``obs`` (an :class:`repro.obs.MiningObs`) records per-level job counters
    (candidates generated / frequent survivors) and the candidate-generation
    phase time — observation only, the mined dicts are identical with obs
    on/off.
    """
    min_count = max(1, math.ceil(cfg.min_support * n))
    levels = dict(resume_state["levels"]) if resume_state else {}
    start_k = resume_state["next_k"] if resume_state else 1

    if start_k <= 1:
        # level 1: supports of singletons — the same count path (uniform Map/Reduce)
        with phase(obs, "level", "candidate_gen"):
            singles = enc.singleton_itemsets(num_items)
        if obs is not None:
            obs.on_level_start(1, singles.shape[0])
        sup1 = count_fn(singles, 1)
        keep = sup1 >= min_count
        levels[1] = (singles[keep], sup1[keep])
        if obs is not None:
            obs.on_level_end(1, int(keep.sum()))
        if checkpoint_cb:
            checkpoint_cb(1, levels)
        start_k = 2

    for k in range(start_k, cfg.max_k + 1):
        prev_sets = levels.get(k - 1, (np.zeros((0, k - 1), np.int32),))[0]
        if prev_sets.shape[0] < k:   # cannot form a k-itemset
            break
        with phase(obs, "level", "candidate_gen"):
            if cfg.use_naive_paper_map:
                # paper §3.3: enumerate every k-subset of the (frequent) item universe
                freq_items = levels[1][0].ravel()
                combos = cand_mod.all_k_subsets_of_universe(freq_items.size, k)
                cands = freq_items[combos]
            else:
                cands = cand_mod.generate_candidates(prev_sets)
        if cands.shape[0] == 0:
            break
        if obs is not None:
            obs.on_level_start(k, cands.shape[0])
        sup = count_fn(cands, k)
        keep = sup >= min_count
        if obs is not None:
            obs.on_level_end(k, int(keep.sum()))
        if not keep.any():
            break
        levels[k] = (cands[keep], sup[keep])
        if checkpoint_cb:
            checkpoint_cb(k, levels)

    return AprioriResult(levels=levels, num_transactions=n, min_count=min_count)


def mine(
    transactions_dense,
    cfg: AprioriConfig = AprioriConfig(),
    mesh: jax.sharding.Mesh | None = None,
    checkpoint_cb: Callable | None = None,
    resume_state: dict | None = None,
) -> AprioriResult:
    """Level-wise distributed Apriori over a dense {0,1} transaction matrix.

    checkpoint_cb(level_k, levels_dict): called after each completed level —
    the mining checkpoint hook (restartable via ``resume_state`` =
    {'levels': ..., 'next_k': ...}, see distributed.fault_tolerance).
    """
    t_np = np.asarray(transactions_dense, dtype=np.int8)
    n, num_items = t_np.shape

    # --- encode + place the DB once: row-sharded over the data axes (HDFS
    # layout); packed uint32 bitsets stay device-resident for the whole loop
    t_dev = place_db(t_np, cfg, mesh)
    count_step = make_count_step(mesh, cfg)

    def count_fn(cand_sets, level_k):
        return _count_level(count_step, t_dev, cand_sets, num_items, cfg, mesh)

    return run_level_loop(count_fn, n, num_items, cfg, checkpoint_cb, resume_state)
