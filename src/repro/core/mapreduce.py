"""Generic Map/Combine/Reduce engine over ``jax.shard_map``.

The paper's Hadoop pipeline is:  map over HDFS partitions -> local combine ->
hash shuffle -> reduce per key.  On a TPU mesh the key space is dense (tensor
indices), so the shuffle+reduce degenerates to a single ``lax.psum`` (or
pmax/pmin) over the data axes — see DESIGN.md §2.  This module is the reusable
engine; ``core.apriori`` instantiates it for support counting, and
:func:`hierarchical_psum` models the paper's rack-local combiner tier.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_REDUCERS = {
    "sum": jax.lax.psum,
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
}


@dataclasses.dataclass(frozen=True)
class MapReduceJob:
    """A Hadoop-style job description.

    map_fn:      per-shard function ``(*shard_args) -> pytree`` — the map task
                 with its combiner already folded in (emit *partial sums*, not
                 per-record pairs; Hadoop combiners do the same on each node).
    reduce_axes: mesh axes over which partials are reduced (the shuffle).
    reduce_op:   'sum' | 'max' | 'min'.
    """

    map_fn: Callable[..., Any]
    reduce_axes: tuple[str, ...]
    reduce_op: str = "sum"


def mapreduce(
    job: MapReduceJob,
    mesh: jax.sharding.Mesh,
    *,
    in_specs: Sequence[P],
    out_specs: Any = P(),
    jit: bool = True,
) -> Callable[..., Any]:
    """Compile a MapReduceJob onto a mesh.

    Returns ``fn(*global_args) -> reduced pytree``. ``out_specs`` must mark the
    result replicated over ``reduce_axes`` (default: fully replicated); result
    may remain sharded over other axes (e.g. the candidate axis over 'model').
    """
    if job.reduce_op not in _REDUCERS:
        raise ValueError(f"unknown reduce_op {job.reduce_op!r}")
    reducer = _REDUCERS[job.reduce_op]
    axes = tuple(job.reduce_axes)

    def _mapper(*args):
        partial = job.map_fn(*args)
        return jax.tree.map(lambda x: reducer(x, axes), partial)

    # check_vma=False: map functions call Pallas kernels, and pallas_call's
    # out_shape carries no mesh-axis variance (vma) for the check to type;
    # the job states its reduction itself (reduce_axes + out_specs)
    fn = jax.shard_map(_mapper, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn) if jit else fn


def hierarchical_psum(
    x: Any,
    inner_axes: tuple[str, ...],
    outer_axes: tuple[str, ...] = (),
    outer_transform: tuple[Callable, Callable] | None = None,
) -> Any:
    """Two-level reduction: psum within ``inner_axes`` (fast ICI), then over
    ``outer_axes`` (slow DCN), optionally transforming the payload for the
    outer hop (e.g. quantizing partial counts before the cross-pod hop).

    Must be called inside a shard_map body.
    """
    y = jax.tree.map(lambda v: jax.lax.psum(v, inner_axes), x) if inner_axes else x
    if not outer_axes:
        return y
    if outer_transform is None:
        return jax.tree.map(lambda v: jax.lax.psum(v, outer_axes), y)
    encode, decode = outer_transform
    enc = encode(y)
    red = jax.tree.map(lambda v: jax.lax.psum(v, outer_axes), enc)
    return decode(red)


def shard_rows(mesh: jax.sharding.Mesh, axes: tuple[str, ...]) -> jax.sharding.NamedSharding:
    """Sharding for a row-partitioned 2-D dataset (the HDFS block layout)."""
    return jax.sharding.NamedSharding(mesh, P(axes, None))


def pad_rows_to_shards(arr: jnp.ndarray, num_shards: int):
    """Pad axis 0 to a multiple of num_shards with zero rows.

    Zero transaction rows are inert for support counting in both device
    representations: dense — every real candidate has |c| >= 1 and
    <0-row, c> == 0 != |c|; packed uint32 — a zero row misses every set
    candidate bit, so ``t & c == c`` fails (DESIGN.md §3). The row partition
    is payload-agnostic: P(data_axes, None) over int8 items or uint32 words
    alike. Returns (padded, original_n).
    """
    import numpy as np

    n = arr.shape[0]
    rem = (-n) % num_shards
    if rem == 0:
        return arr, n
    pad = np.zeros((rem,) + arr.shape[1:], dtype=arr.dtype)
    return np.concatenate([np.asarray(arr), pad], axis=0), n
