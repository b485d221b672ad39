"""Batched basket -> recommendation query engine (DESIGN.md §8).

The serving loop over a compiled rulebook: baskets are packed to the uint32
bitset word layout, streamed through the rule-match kernel in fixed-size
batches (one jit bucket), and each basket's per-item evidence scores are
reduced to top-k item recommendations with ``lax.top_k`` — items already in
the basket are masked to ``-inf`` first (you don't recommend what the user
already has) unless ``exclude_basket=False``.

On a mesh, the match step is the same Map/Reduce shape as mining, flipped:
baskets row-shard over the data axes (the query "HDFS blocks") while the
rulebook row-shards over ``rule_axis`` — each device matches its rule slice
against its basket shard and a ``lax.psum`` over the rule axis assembles the
full (B, I) score matrix (``core.mapreduce.MapReduceJob``, reduce over the
*model* axis where mining reduces over *data*).

``recommend_python`` is the per-basket pure-Python engine — the oracle for
tests and the baseline the serving benchmark measures QPS against.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import itemsets as enc
from repro.core.mapreduce import MapReduceJob, mapreduce
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.serving.rulebook import Rulebook


@dataclasses.dataclass
class RecommendResult:
    """Top-k recommendations per basket.  ``scores == -inf`` marks slots
    beyond the basket's candidate items (k larger than what's scoreable)."""

    items: np.ndarray    # (B, top_k) int32 item ids
    scores: np.ndarray   # (B, top_k) float32 aggregated rule evidence


def pack_baskets(baskets, num_items: int) -> np.ndarray:
    """Item-id lists or a dense {0,1} matrix -> packed uint32 (B, W) bitsets.

    A 2-D ndarray is always the dense form and must be exactly ``num_items``
    wide — a mismatched matrix is an error, never reinterpreted as id lists
    (a {0,1} row read as item ids would silently score garbage)."""
    if isinstance(baskets, np.ndarray) and baskets.ndim == 2:
        if baskets.shape[1] != num_items:
            raise ValueError(
                f"dense baskets are {baskets.shape[1]} items wide but the "
                f"rulebook vocabulary is {num_items}"
            )
        return enc.pack_bits(baskets)
    return enc.pack_bits(enc.dense_from_lists(list(baskets), num_items))


@functools.lru_cache(maxsize=16)
def _cached_match_step(mesh, impl, data_axes, rule_axis, block_n, block_k):
    return make_match_step(
        mesh, impl=impl, data_axes=data_axes, rule_axis=rule_axis,
        block_n=block_n, block_k=block_k,
    )


def make_match_step(
    mesh=None,
    *,
    impl: str = "auto",
    data_axes: tuple = ("data",),
    rule_axis: str = "model",
    block_n: int = 256,
    block_k: int = 256,
):
    """Build the jit'd batched match step:
    ``fn(b_packed (B, W), ante, lens, cons, scores) -> (B, 32·W) float32``.

    Single-device: a jit around ``kernels.ops.rule_match``.  Mesh: the
    Map/Reduce form — baskets sharded ``P(data_axes, None)``, rulebook
    columns ``P(rule_axis, ...)``, partial item scores psum'd over the rule
    axis (replicated result rows stay sharded over the data axes). Its ops
    carry the name scope ``repro.rule_match``, whatever implements the match.
    """
    def local_match(b, a, ln, c, s):
        with jax.named_scope("repro.rule_match"):
            return kops.rule_match(b, a, ln, c, s, impl=impl, block_n=block_n,
                                   block_k=block_k)

    if mesh is None or math.prod(mesh.shape.values()) == 1:
        return jax.jit(local_match)

    job = MapReduceJob(map_fn=local_match, reduce_axes=(rule_axis,))
    in_specs = (
        P(data_axes, None),       # baskets: query row partition
        P(rule_axis, None),       # antecedent bitsets
        P(rule_axis),             # antecedent lengths
        P(rule_axis, None),       # consequent bitsets
        P(rule_axis),             # score column
    )
    return mapreduce(job, mesh, in_specs=in_specs, out_specs=P(data_axes, None))


@functools.partial(jax.jit, static_argnames=("top_k", "exclude_basket", "num_items"))
def _topk_items(item_scores, b_packed, *, top_k, exclude_basket, num_items):
    item_scores = item_scores[:, :num_items]
    if exclude_basket:
        in_basket = kref.unpack_bits_ref(b_packed, num_items) > 0
        item_scores = jnp.where(in_basket, -jnp.inf, item_scores)
    vals, idx = jax.lax.top_k(item_scores, top_k)
    return idx.astype(jnp.int32), vals


def recommend(
    rb: Rulebook,
    baskets,
    *,
    top_k: int = 10,
    batch_size: int = 1024,
    impl: str = "auto",
    exclude_basket: bool = True,
    mesh=None,
    data_axes: tuple = ("data",),
    rule_axis: str = "model",
    match_step=None,
    block_n: int = 256,
    block_k: int = 256,
) -> RecommendResult:
    """Batched end-to-end query loop: pack -> match -> mask -> top-k.

    ``baskets``: item-id lists, a dense {0,1} matrix, or pre-packed uint32
    bitsets.  Every batch is padded to ``batch_size`` (zero baskets are
    inert), so the whole stream compiles exactly one match-step bucket.
    Pass ``match_step`` to reuse a step across calls (e.g. a mesh-compiled
    one); otherwise one is built from ``mesh``/``impl``.
    """
    w = enc.packed_words(rb.num_items)
    b_np = np.asarray(baskets) if not isinstance(baskets, (list, tuple)) else None
    if b_np is not None and b_np.dtype == np.uint32 and b_np.ndim == 2 and b_np.shape[1] == w:
        b_packed = b_np
    else:
        b_packed = pack_baskets(baskets, rb.num_items)
    n = b_packed.shape[0]
    top_k = min(top_k, rb.num_items)

    if mesh is not None:
        shards = math.prod(mesh.shape[a] for a in data_axes)
        batch_size = ((batch_size + shards - 1) // shards) * shards
        if not isinstance(rb.ante_packed, jax.Array):
            from repro.serving.rulebook import place_rulebook

            rb = place_rulebook(rb, mesh, rule_axis)
        basket_sharding = NamedSharding(mesh, P(data_axes, None))
    elif not isinstance(rb.ante_packed, jax.Array):
        from repro.serving.rulebook import place_rulebook

        # commit the columns to device ONCE — not re-uploaded per batch
        rb = place_rulebook(rb, None)
    # cached per (mesh, impl, axes, blocks): repeated recommend() calls hit
    # the same jit entry instead of re-tracing the serving hot path
    step = match_step or _cached_match_step(
        mesh, impl, tuple(data_axes), rule_axis, block_n, block_k
    )

    items_out = np.zeros((n, top_k), np.int32)
    scores_out = np.zeros((n, top_k), np.float32)
    for start in range(0, n, batch_size):
        blk = b_packed[start : start + batch_size]
        m = blk.shape[0]
        if m < batch_size:
            blk = np.pad(blk, ((0, batch_size - m), (0, 0)))
        if mesh is not None:
            blk_dev = jax.device_put(blk, basket_sharding)
        else:
            blk_dev = jnp.asarray(blk)
        item_scores = step(blk_dev, rb.ante_packed, rb.ante_len, rb.cons_packed, rb.scores)
        idx, vals = _topk_items(
            item_scores, blk_dev,
            top_k=top_k, exclude_basket=exclude_basket, num_items=rb.num_items,
        )
        items_out[start : start + m] = np.asarray(idx)[:m]
        scores_out[start : start + m] = np.asarray(vals)[:m]
    return RecommendResult(items=items_out, scores=scores_out)


def rulebook_as_python(rb: Rulebook) -> list[tuple[frozenset, np.ndarray, float]]:
    """Decode a rulebook into (antecedent set, consequent item ids, score)
    triples — the working set of :func:`recommend_python`."""
    lens = np.asarray(rb.ante_len)
    keep = lens >= 0
    ante = enc.unpack_bits(np.asarray(rb.ante_packed)[keep], rb.num_items)
    cons = enc.unpack_bits(np.asarray(rb.cons_packed)[keep], rb.num_items)
    scores = np.asarray(rb.scores)[keep]
    return [
        (frozenset(np.flatnonzero(a).tolist()), np.flatnonzero(c), float(s))
        for a, c, s in zip(ante, cons, scores)
    ]


def recommend_python(
    rb: Rulebook,
    baskets,
    *,
    top_k: int = 10,
    exclude_basket: bool = True,
    decoded=None,
) -> RecommendResult:
    """Naive per-basket rule matching — oracle and QPS baseline.

    Same semantics as :func:`recommend`: summed score evidence per
    consequent item over matched rules, basket items masked to ``-inf``,
    ties broken by lowest item id (matching ``lax.top_k``).
    """
    rules = rulebook_as_python(rb) if decoded is None else decoded
    if isinstance(baskets, np.ndarray) and baskets.dtype == np.uint32:
        baskets = enc.unpack_bits(baskets, rb.num_items)
    if isinstance(baskets, np.ndarray) and baskets.ndim == 2:
        baskets = [np.flatnonzero(row).tolist() for row in np.asarray(baskets)]
    top_k = min(top_k, rb.num_items)

    items_out = np.zeros((len(baskets), top_k), np.int32)
    scores_out = np.zeros((len(baskets), top_k), np.float32)
    for b, basket in enumerate(baskets):
        bset = set(int(x) for x in basket)
        acc = np.zeros(rb.num_items, np.float64)
        for ante, cons, score in rules:
            if ante <= bset:
                acc[cons] += score
        if exclude_basket:
            acc[sorted(bset)] = -np.inf
        idx = np.lexsort((np.arange(rb.num_items), -acc))[:top_k]
        items_out[b] = idx
        scores_out[b] = acc[idx]
    return RecommendResult(items=items_out, scores=scores_out)
