"""Pallas TPU kernel: batched basket->rule matching + per-item score fan-out.

The serving half of the pipeline (DESIGN.md §8).  A compiled rulebook
(``serving/rulebook.py``) is four device-resident columns in the packed
uint32 word layout of ``support_count_packed.py``:

    a_packed (R, W) uint32   antecedent bitsets
    c_packed (R, W) uint32   consequent bitsets
    lengths  (R,)   int32    antecedent popcounts (-1 = padding row)
    scores   (R,)   float32  rule weight (confidence / lift, 0 on padding)

For a batch of basket bitsets ``b_packed (B, W)`` the kernel computes, in one
fused pass per (basket-block, rule-block) tile:

    matched[b, r] = (∀w: b[b,w] & a[r,w] == a[r,w]) ∧ lengths[r] >= 0
    out[b, i]     = Σ_r matched[b, r] · scores[r] · cons_bit[r, i]

i.e. antecedent containment is the same VPU bitwise test as the packed
counting kernel, and the per-item aggregation is an MXU matmul of the masked
score matrix against the consequent bitsets unpacked in-register to a
(bk, 32·W) {0,1} operand — summed evidence per item, never a sparse scatter.
Top-k item selection happens outside the kernel (``kernels.ops.rule_match``
returns the dense (B, I) score matrix; ``serving/recommend.py`` applies
basket-exclusion masking + ``lax.top_k``).

Grid = (B/bn, R/bk); the word axis stays whole inside the body (serving
vocabularies keep W = ceil(I/32) small — 32 words at I = 1024) as a static
Python unroll, so no cross-tile accumulator state is needed: the output
block is revisited (accumulated) only across the rule grid dimension.

Padding semantics (DESIGN.md §3): padded baskets are zero rows — a real
antecedent has ≥ 1 set bit they lack, and their output rows are sliced off
by the wrapper anyway; padded rules are zero rows with ``len = -1`` *and*
``score = 0`` (masked twice over).  VMEM per step at (bn, bk, W) =
(256, 256, 32): two uint32 rule blocks 64 KB + basket block 32 KB + the
(bn, 32·W) f32 output and unpacked operand 1 MB each — comfortably under
budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(b_ref, a_ref, len_ref, c_ref, score_ref, out_ref, *, num_words):
    r = pl.program_id(1)

    b = b_ref[...]  # (bn, W) uint32
    a = a_ref[...]  # (bk, W) uint32

    # --- antecedent containment: count violated words (packed-kernel test) ---
    viol = jnp.zeros((b.shape[0], a.shape[0]), jnp.int32)
    for w in range(num_words):
        bw = b[:, w : w + 1]        # (bn, 1)
        aw = a[:, w : w + 1].T      # (1, bk)
        viol += ((bw & aw) != aw).astype(jnp.int32)
    matched = (viol == 0) & (len_ref[...] >= 0)            # (bn, bk)
    weights = matched.astype(jnp.float32) * score_ref[...]  # (bn, bk)

    # --- consequent fan-out: unpack bitsets in-register, one MXU matmul ---
    # (Mosaic has no uint32 -> f32 cast; the bit is 0/1, so go through int32)
    c = c_ref[...]  # (bk, W) uint32
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 32), 1)
    cols = [
        ((c[:, w : w + 1] >> shifts) & jnp.uint32(1)).astype(jnp.int32).astype(jnp.float32)
        for w in range(num_words)
    ]
    cons_dense = jnp.concatenate(cols, axis=1)  # (bk, 32·W) — little-endian items
    # HIGHEST: the weights are f32 scores, and a reduced-precision pass
    # would round them on the MXU
    contrib = jnp.dot(weights, cons_dense, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)

    @pl.when(r == 0)
    def _init():
        out_ref[...] = contrib

    @pl.when(r > 0)
    def _accum():
        out_ref[...] += contrib


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_k", "interpret")
)
def rule_match_pallas(
    b_packed: jax.Array,
    a_packed: jax.Array,
    lengths: jax.Array,
    c_packed: jax.Array,
    scores: jax.Array,
    *,
    block_n: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Per-item rule-evidence scores (B, 32·W) float32 for pre-padded
    operands: B % block_n == R % block_k == 0 (use ``kernels.ops.rule_match``
    for the padding/dispatch wrapper)."""
    n, w = b_packed.shape
    r, w2 = a_packed.shape
    assert w == w2 and c_packed.shape == (r, w)
    assert lengths.shape == (r,) and scores.shape == (r,)
    assert b_packed.dtype == jnp.uint32 and a_packed.dtype == jnp.uint32
    assert n % block_n == 0 and r % block_k == 0, (
        f"operands must be pre-padded: {(n, r)} vs blocks {(block_n, block_k)}"
    )

    len2d = lengths.astype(jnp.int32).reshape(1, r)
    score2d = scores.astype(jnp.float32).reshape(1, r)
    grid = (n // block_n, r // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, num_words=w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, w), lambda nn, rr: (nn, 0)),
            pl.BlockSpec((block_k, w), lambda nn, rr: (rr, 0)),
            pl.BlockSpec((1, block_k), lambda nn, rr: (0, rr)),
            pl.BlockSpec((block_k, w), lambda nn, rr: (rr, 0)),
            pl.BlockSpec((1, block_k), lambda nn, rr: (0, rr)),
        ],
        out_specs=pl.BlockSpec((block_n, 32 * w), lambda nn, rr: (nn, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 32 * w), jnp.float32),
        interpret=interpret,
    )(b_packed, a_packed, len2d, c_packed, score2d)
