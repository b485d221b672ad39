"""Pure-jnp oracles for every Pallas kernel in this package."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def support_count_ref(t_dense, c_dense, lengths):
    """Exact support counts.

    t_dense: (N, I) {0,1} int8 transactions
    c_dense: (K, I) {0,1} int8 candidate itemsets
    lengths: (K,)   int32 itemset sizes (|c| >= 1; padded rows use -1)
    returns: (K,)   int32  —  #transactions t with c ⊆ t
    """
    inter = jnp.matmul(
        t_dense.astype(jnp.int32), c_dense.astype(jnp.int32).T
    )  # (N, K) intersection sizes
    contained = inter == lengths[None, :].astype(jnp.int32)
    return jnp.sum(contained, axis=0, dtype=jnp.int32)


def support_count_packed_ref(t_packed, c_packed, lengths=None, block_k: int = 256):
    """Bitset oracle over packed uint32 words (VPU-style path).

    t_packed: (N, W) uint32, c_packed: (K, W) uint32.
    lengths:  optional (K,) int32 itemset sizes; rows with ``len = -1`` are
              padding and never match (same semantics as the dense path).
              Without lengths, padding rows are encoded as all-ones words.
    Containment: (t & c) == c for every word. Blocked over K to bound memory.
    """
    n, w = t_packed.shape
    k, _ = c_packed.shape
    pad = (-k) % block_k
    c_pad = jnp.pad(c_packed, ((0, pad), (0, 0)), constant_values=jnp.uint32(0xFFFFFFFF))
    valid = None
    if lengths is not None:
        valid = jnp.pad(lengths.astype(jnp.int32), (0, pad), constant_values=-1) >= 0

    def one_block(c_blk):
        # (N, 1, W) & (1, bk, W)
        inter = t_packed[:, None, :] & c_blk[None, :, :]
        contained = jnp.all(inter == c_blk[None, :, :], axis=-1)
        return contained.sum(axis=0, dtype=jnp.int32)

    blocks = c_pad.reshape(-1, block_k, w)
    counts = jax.lax.map(one_block, blocks).reshape(-1)
    if valid is not None:
        counts = jnp.where(valid, counts, 0)
    return counts[:k]


def unpack_bits_ref(packed, num_items: int):
    """Packed uint32 (R, W) -> dense {0,1} float32 (R, num_items) — jnp twin
    of ``core.itemsets.unpack_bits`` (little-endian bits per word)."""
    r, w = packed.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (packed[:, :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(r, w * 32)[:, :num_items].astype(jnp.float32)


def rule_match_ref(b_packed, a_packed, lengths, c_packed, scores):
    """Per-item rule-evidence scores — oracle for ``kernels/rule_match.py``.

    b_packed: (B, W) uint32 basket bitsets
    a_packed: (R, W) uint32 antecedent bitsets
    lengths:  (R,)   int32  antecedent sizes (-1 = padding row, never matches)
    c_packed: (R, W) uint32 consequent bitsets
    scores:   (R,)   float32 rule weights
    returns:  (B, 32·W) float32 — out[b, i] = Σ_r [a_r ⊆ basket_b] · s_r · c_r[i]
    """
    contains = jnp.all(
        (b_packed[:, None, :] & a_packed[None, :, :]) == a_packed[None, :, :], axis=-1
    )  # (B, R)
    matched = contains & (lengths.astype(jnp.int32) >= 0)[None, :]
    weights = matched.astype(jnp.float32) * scores.astype(jnp.float32)[None, :]
    cons_dense = unpack_bits_ref(c_packed, 32 * c_packed.shape[1])  # (R, 32·W)
    # HIGHEST: XLA's default f32 matmul precision on TPU rounds the scores
    return jnp.matmul(weights, cons_dense, precision=jax.lax.Precision.HIGHEST)
