"""Jit'd public wrappers around the Pallas kernels.

Handles shape padding to block multiples, impl dispatch ('auto' resolves to
the Pallas kernel on TPU and the jnp oracle on CPU — interpret-mode Pallas is
kept for tests, where it validates the kernel body semantics), and padding
semantics (padded transactions are zero rows; padded candidates get |c| = -1
so they can never match; packed operands additionally pad the word axis with
zero words — see DESIGN.md §3).

Two counting entry points:
  * :func:`support_count` — dense {0,1} operands. ``impl="packed"`` packs
    them to uint32 bitsets on device and routes through the packed path.
  * :func:`support_count_packed` — pre-packed uint32 operands (the format
    ``core.apriori`` keeps device-resident across the whole level loop).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.support_count import support_count_pallas
from repro.kernels.support_count_packed import support_count_packed_pallas


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def resolve_impl(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


@functools.partial(jax.jit, static_argnames=("num_items",))
def pack_bits_device(dense: jax.Array, num_items: int | None = None) -> jax.Array:
    """Device-side dense {0,1} (R, I) -> packed uint32 (R, ceil(I/32)).

    Little-endian bits per word — the jnp twin of ``core.itemsets.pack_bits``.
    """
    r, i = dense.shape
    if num_items is not None:
        assert i == num_items
    words = (i + 31) // 32
    d = jnp.pad(dense.astype(jnp.uint32), ((0, 0), (0, words * 32 - i)))
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (d.reshape(r, words, 32) << shifts).sum(axis=2, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("num_items",))
def unpack_bits_device(packed: jax.Array, num_items: int) -> jax.Array:
    """Device-side packed (R, W) uint32 -> dense {0,1} int8 (R, num_items).

    Little-endian bits per word — the jnp twin of ``core.itemsets.unpack_bits``.
    """
    r, words = packed.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    dense = (packed[:, :, None] >> shifts) & 1
    return dense.reshape(r, words * 32)[:, :num_items].astype(jnp.int8)


def support_count(
    t_dense,
    c_dense,
    lengths,
    *,
    impl: str = "auto",
    block_n: int = 256,
    block_k: int = 256,
    block_i: int = 512,
    operand_dtype: str = "bf16",
):
    """Support counts of K candidates over N transactions (exact int32).

    Accepts arbitrary (N, I, K); pads to kernel block multiples internally.
    impl: auto | jnp | pallas | pallas_interpret
        | packed | packed_jnp | packed_pallas | packed_interpret
    The packed impls bit-pack the dense operands on device and dispatch to
    :func:`support_count_packed` ('packed' resolves like 'auto').
    """
    impl = resolve_impl(impl)
    n, i = t_dense.shape
    k = c_dense.shape[0]
    if impl == "jnp":
        return ref.support_count_ref(t_dense, c_dense, lengths)
    if impl == "jnp_blocked":
        from repro.kernels.blocked import support_count_blocked

        return support_count_blocked(t_dense, c_dense, lengths)
    if impl == "packed" or impl.startswith("packed_"):
        sub = "auto" if impl == "packed" else impl[len("packed_") :]
        sub = {"interpret": "pallas_interpret"}.get(sub, sub)
        return support_count_packed(
            pack_bits_device(t_dense, i),
            pack_bits_device(c_dense, i),
            lengths,
            impl=sub,
            block_n=block_n,
            block_k=block_k,
        )
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown impl {impl!r}")

    # Shrink blocks for small problems (keep the 128-lane minor alignment).
    block_n = min(block_n, _round_up(n, 8))
    block_k = min(block_k, _round_up(k, 128))
    block_i = min(block_i, _round_up(i, 128))
    np_, kp, ip = _round_up(n, block_n), _round_up(k, block_k), _round_up(i, block_i)
    t_p = jnp.pad(t_dense, ((0, np_ - n), (0, ip - i)))
    c_p = jnp.pad(c_dense, ((0, kp - k), (0, ip - i)))
    len_p = jnp.pad(lengths.astype(jnp.int32), (0, kp - k), constant_values=-1)
    counts = support_count_pallas(
        t_p,
        c_p,
        len_p,
        block_n=block_n,
        block_k=block_k,
        block_i=block_i,
        operand_dtype=operand_dtype,
        interpret=(impl == "pallas_interpret"),
    )
    return counts[:k]


def support_count_packed(
    t_packed,
    c_packed,
    lengths,
    *,
    impl: str = "auto",
    block_n: int = 256,
    block_k: int = 256,
    mode: str = "and_cmp",
):
    """Support counts over packed uint32 bitset operands (exact int32).

    t_packed: (N, W) uint32, c_packed: (K, W) uint32, lengths: (K,) int32
    with |c| = -1 marking padded candidate rows. Accepts arbitrary (N, W, K);
    pads rows/candidates to kernel block multiples internally (zero rows /
    -1 lengths — inert, DESIGN.md §3). The word axis is tiled in 128-lane
    slabs when W is a multiple of 128 and taken whole otherwise: Mosaic
    accepts no other minor block.
    impl: auto | jnp | pallas | pallas_interpret
    """
    impl = resolve_impl(impl)
    n, w = t_packed.shape
    k = c_packed.shape[0]
    if impl == "jnp":
        return ref.support_count_packed_ref(t_packed, c_packed, lengths)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown packed impl {impl!r}")

    block_n = min(block_n, _round_up(n, 8))
    block_k = min(block_k, _round_up(k, 128))
    block_w = w if w % 128 else 128
    np_, kp = _round_up(n, block_n), _round_up(k, block_k)
    t_p = jnp.pad(t_packed, ((0, np_ - n), (0, 0)))
    c_p = jnp.pad(c_packed, ((0, kp - k), (0, 0)))
    len_p = jnp.pad(lengths.astype(jnp.int32), (0, kp - k), constant_values=-1)
    counts = support_count_packed_pallas(
        t_p,
        c_p,
        len_p,
        block_n=block_n,
        block_k=block_k,
        block_w=block_w,
        mode=mode,
        interpret=(impl == "pallas_interpret"),
    )
    return counts[:k]


@functools.partial(jax.jit, static_argnames=("block_n",))
def _rule_match_jnp_blocked(b_packed, a_packed, lengths, c_packed, scores, block_n=512):
    """Basket-blocked oracle dispatch: bounds the (bn, R, W) broadcast the
    plain reference materializes, so the jnp path serves large batches
    without an O(B·R·W) intermediate."""
    n, w = b_packed.shape
    pad = (-n) % block_n
    b_p = jnp.pad(b_packed, ((0, pad), (0, 0)))  # zero baskets match nothing real

    def one_block(b_blk):
        return ref.rule_match_ref(b_blk, a_packed, lengths, c_packed, scores)

    out = jax.lax.map(one_block, b_p.reshape(-1, block_n, w))
    return out.reshape(-1, 32 * w)[:n]


def rule_match(
    b_packed,
    a_packed,
    lengths,
    c_packed,
    scores,
    *,
    num_items: int | None = None,
    impl: str = "auto",
    block_n: int = 256,
    block_k: int = 256,
):
    """Per-item rule-evidence scores for a batch of basket bitsets.

    b_packed: (B, W) uint32; a_packed/c_packed: (R, W) uint32 rulebook
    columns; lengths: (R,) int32 antecedent sizes (-1 = padding row);
    scores: (R,) float32.  Returns (B, num_items or 32·W) float32 where
    ``out[b, i] = Σ_r [antecedent_r ⊆ basket_b] · scores[r] · consequent_r[i]``.
    Accepts arbitrary (B, R); pads to kernel block multiples internally
    (zero basket rows / zero rule rows with len = -1 and score 0 — inert).
    impl: auto | jnp | pallas | pallas_interpret
    """
    impl = resolve_impl(impl)
    n, w = b_packed.shape
    r = a_packed.shape[0]
    assert a_packed.shape == (r, w) and c_packed.shape == (r, w), (
        "basket and rulebook word counts must agree"
    )
    items = 32 * w if num_items is None else num_items
    if impl == "jnp":
        # honor the caller's basket block, capped at the (padded) batch so
        # small batches don't broadcast/matmul against a full default block
        bn = min(max(block_n, 8), _round_up(n, 8))
        out = _rule_match_jnp_blocked(
            b_packed, a_packed, lengths, c_packed, scores, block_n=bn
        )
        return out[:, :items]
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown rule_match impl {impl!r}")

    block_n = min(block_n, _round_up(n, 8))
    block_k = min(block_k, _round_up(r, 128))
    np_, rp = _round_up(n, block_n), _round_up(r, block_k)
    b_p = jnp.pad(b_packed, ((0, np_ - n), (0, 0)))
    a_p = jnp.pad(a_packed, ((0, rp - r), (0, 0)))
    c_p = jnp.pad(c_packed, ((0, rp - r), (0, 0)))
    len_p = jnp.pad(lengths.astype(jnp.int32), (0, rp - r), constant_values=-1)
    score_p = jnp.pad(scores.astype(jnp.float32), (0, rp - r))
    from repro.kernels.rule_match import rule_match_pallas

    out = rule_match_pallas(
        b_p, a_p, len_p, c_p, score_p,
        block_n=block_n, block_k=block_k,
        interpret=(impl == "pallas_interpret"),
    )
    return out[:n, :items]
