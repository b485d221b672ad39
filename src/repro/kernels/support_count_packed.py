"""Pallas TPU kernel: candidate-support counting over packed uint32 bitsets.

The dense kernel (``support_count.py``) spends MXU flops and HBM bandwidth on
a {0,1} matrix that carries one bit of information per 8–16-bit cell.  This
kernel is the roofline-correct representation (DESIGN.md §4): transactions
and candidates are packed little-endian into uint32 words, shrinking the item
axis 8–32× in bytes, and containment is a VPU bitwise test instead of a
matmul::

    c ⊆ t   ⟺   ∀w: t[n,w] & c[k,w] == c[k,w]
            ⟺   Σ_w popcount(t[n,w] & c[k,w]) == |c_k|      (popcount mode)

Grid = (K/bk, N/bn, W/bw), word-slabs innermost so a VMEM scratch accumulator
(`bn × bk` int32) carries the per-pair word state across W tiles; at the last
W tile the epilogue folds per-transaction containment into the output block,
which is revisited (accumulated) across the N grid dimension — the same
revisit/accumulate structure as the dense kernel, so the two are drop-in
interchangeable behind ``kernels.ops``.

Two containment modes:
  * ``and_cmp`` (default): the accumulator counts *violated* words
    (``t & c != c``); a candidate is contained iff zero violations.  Pure
    bitwise AND + compare — the cheapest VPU path.
  * ``popcount``: the accumulator sums intersection popcounts and the
    epilogue compares against ``|c|`` — bit-for-bit the dense kernel's
    semantics, useful for cross-checking and for future weighted variants.

Padding semantics match the dense kernel exactly: padded transactions are
zero rows (zero words — inert: any real candidate has a set bit they lack);
padded candidates are zero rows with ``len = -1`` (``and_cmp`` masks them via
``len >= 0``, ``popcount`` can never reach -1).  The word axis pads with zero
words on both operands, which perturbs neither test.

Contract (same as the dense kernel): ``lengths[k]`` must equal the true
popcount of ``c_packed[k]`` (or -1 for padding).  The modes diverge only on
*inconsistent* inputs — e.g. a zero-bit candidate labelled ``len = 1`` is
"contained nowhere" under dense/``popcount`` but "contained everywhere"
under ``and_cmp``, which never inspects the length's magnitude.

The per-tile word loop is a *static* Python unroll over ``block_w`` lane
slices — no dynamic lane indexing, which keeps the Mosaic lowering to plain
VPU ops.  Mosaic takes a minor block that is the whole word axis or a
multiple of 128 lanes, so ``kernels.ops`` passes ``block_w = W`` unless W is
a multiple of 128.  VMEM per step = bn·bw·4 + bk·bw·4 + bn·bk·4; at
(256, 256, 32) that is ≈ 0.33 MB, far under budget, leaving room for double
buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MODES = ("and_cmp", "popcount")


def _kernel(t_ref, c_ref, len_ref, out_ref, acc_ref, *, block_w, mode):
    w = pl.program_id(2)
    n = pl.program_id(1)
    num_w = pl.num_programs(2)

    @pl.when(w == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = t_ref[...]  # (bn, bw) uint32
    c = c_ref[...]  # (bk, bw) uint32

    # Static unroll over the word slab: each step is an outer (bn, bk)
    # broadcast of one transaction word column against one candidate word row.
    acc = acc_ref[...]
    for j in range(block_w):
        tw = t[:, j : j + 1]        # (bn, 1)
        cw = c[:, j : j + 1].T      # (1, bk)
        inter = tw & cw
        if mode == "popcount":
            acc += jax.lax.population_count(inter).astype(jnp.int32)
        else:
            acc += (inter != cw).astype(jnp.int32)  # violated words
    acc_ref[...] = acc

    @pl.when(w == num_w - 1)
    def _epilogue():
        lengths = len_ref[...]  # (1, bk) int32
        if mode == "popcount":
            contained = acc_ref[...] == lengths
        else:
            contained = (acc_ref[...] == 0) & (lengths >= 0)
        cnt = contained.astype(jnp.int32).sum(axis=0, keepdims=True)  # (1, bk)

        @pl.when(n == 0)
        def _init():
            out_ref[...] = cnt

        @pl.when(n > 0)
        def _accum():
            out_ref[...] += cnt


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_k", "block_w", "mode", "interpret"),
)
def support_count_packed_pallas(
    t_packed: jax.Array,
    c_packed: jax.Array,
    lengths: jax.Array,
    *,
    block_n: int = 256,
    block_k: int = 256,
    block_w: int = 128,
    mode: str = "and_cmp",
    interpret: bool = False,
) -> jax.Array:
    """Counts for pre-padded packed operands: N % block_n == K % block_k ==
    W % block_w == 0, with block_w == W or a multiple of 128 on the chip
    (use kernels.ops.support_count_packed for the padding/packing wrapper).
    """
    n, w = t_packed.shape
    k, w2 = c_packed.shape
    assert w == w2 and lengths.shape == (k,)
    assert t_packed.dtype == jnp.uint32 and c_packed.dtype == jnp.uint32
    assert n % block_n == 0 and k % block_k == 0 and w % block_w == 0, (
        f"operands must be pre-padded: {(n, k, w)} vs blocks {(block_n, block_k, block_w)}"
    )
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")

    len2d = lengths.astype(jnp.int32).reshape(1, k)
    grid = (k // block_k, n // block_n, w // block_w)
    out = pl.pallas_call(
        functools.partial(_kernel, block_w=block_w, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_w), lambda kk, nn, ww: (nn, ww)),
            pl.BlockSpec((block_k, block_w), lambda kk, nn, ww: (kk, ww)),
            pl.BlockSpec((1, block_k), lambda kk, nn, ww: (0, kk)),
        ],
        out_specs=pl.BlockSpec((1, block_k), lambda kk, nn, ww: (0, kk)),
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_n, block_k), jnp.int32)],
        interpret=interpret,
    )(t_packed, c_packed, len2d)
    return out.reshape(k)
