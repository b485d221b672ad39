"""Packed uint32 bitset counting path: encoding helpers, dispatch parity,
padding invariants, and end-to-end mine() equivalence (DESIGN.md §4)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.itemsets import (
    itemsets_to_dense,
    itemsets_to_packed,
    pack_bits,
    packed_words,
    pad_packed,
    unpack_bits,
)
from repro.kernels import ops, ref

from conftest import random_problem as _random_problem


# ----------------------------------------------------------- encodings -------
def test_packed_words():
    assert [packed_words(x) for x in (1, 31, 32, 33, 64, 100)] == [1, 1, 1, 2, 2, 4]


@pytest.mark.parametrize("num_items", [7, 32, 33, 96, 130])
def test_itemsets_to_packed_matches_dense_pack(num_items):
    rng = np.random.default_rng(num_items)
    sets = np.sort(
        rng.choice(num_items, size=(20, min(4, num_items)), replace=True), axis=1
    ).astype(np.int32)
    np.testing.assert_array_equal(
        itemsets_to_packed(sets, num_items), pack_bits(itemsets_to_dense(sets, num_items))
    )


def test_itemsets_to_packed_rejects_out_of_range():
    with pytest.raises(ValueError):
        itemsets_to_packed(np.array([[0, 5]], np.int32), 5)


def test_pad_packed_is_inert():
    t, c, lengths = _random_problem(30, 40, 9, seed=1)
    tp, cp = pack_bits(t), pack_bits(c)
    want = np.asarray(ref.support_count_ref(jnp.asarray(t), jnp.asarray(c), jnp.asarray(lengths)))
    tp_pad = pad_packed(tp, row_multiple=16, word_multiple=4)  # zero rows + words
    cp_pad = pad_packed(cp, word_multiple=4)
    got = np.asarray(
        ops.support_count_packed(jnp.asarray(tp_pad), jnp.asarray(cp_pad), jnp.asarray(lengths), impl="jnp")
    )
    np.testing.assert_array_equal(got, want)


def test_pack_bits_device_matches_host():
    rng = np.random.default_rng(3)
    for i in (17, 32, 75, 128):
        dense = (rng.random((13, i)) < 0.4).astype(np.int8)
        np.testing.assert_array_equal(
            np.asarray(ops.pack_bits_device(jnp.asarray(dense), i)), pack_bits(dense)
        )
        np.testing.assert_array_equal(unpack_bits(pack_bits(dense), i), dense)


@pytest.mark.parametrize("num_items", [1, 31, 32, 33, 45, 1000])
def test_unpack_bits_device_matches_host(num_items):
    """The device unpack of the store's uint32 words is the host's
    ``unpack_bits``, with an all-zero padding row unpacking to an all-zero
    row."""
    rng = np.random.default_rng(num_items)
    dense = (rng.random((9, num_items)) < 0.4).astype(np.int8)
    dense[4] = 0
    words = pack_bits(dense)
    got = np.asarray(ops.unpack_bits_device(jnp.asarray(words), num_items))
    assert got.dtype == np.int8 and got.shape == dense.shape
    np.testing.assert_array_equal(got, unpack_bits(words, num_items))
    np.testing.assert_array_equal(got, dense)


# ---------------------------------------------------- dispatch parity --------
RANDOM_SHAPES = [
    (8, 16, 4),       # tiny
    (100, 37, 33),    # I not a multiple of 32
    (200, 96, 50),    # word-aligned I
    (130, 257, 70),   # multi-word, ragged everywhere
    (64, 31, 128),    # single partial word
]


@pytest.mark.parametrize("shape", RANDOM_SHAPES)
def test_packed_impl_matches_ref_and_dense_pallas(shape):
    """support_count(impl='packed') == dense oracle == dense Pallas interpret."""
    n, i, k = shape
    t, c, lengths = _random_problem(n, i, k, seed=sum(shape))
    tj, cj, lj = jnp.asarray(t), jnp.asarray(c), jnp.asarray(lengths)
    want = np.asarray(ref.support_count_ref(tj, cj, lj))
    got_packed = np.asarray(ops.support_count(tj, cj, lj, impl="packed"))
    np.testing.assert_array_equal(got_packed, want)
    got_dense_pallas = np.asarray(
        ops.support_count(tj, cj, lj, impl="pallas_interpret", block_n=64, block_k=128, block_i=128)
    )
    np.testing.assert_array_equal(got_packed, got_dense_pallas)


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_packed_all_padding_candidate_rows(impl):
    """A pass whose candidate rows are ALL padding (len = -1, zero words)
    must count zero — padded rows can never match any transaction."""
    t, _, _ = _random_problem(40, 64, 4, seed=9)
    k = 12
    cp = np.zeros((k, packed_words(64)), np.uint32)
    lengths = np.full(k, -1, np.int32)
    got = np.asarray(
        ops.support_count_packed(
            jnp.asarray(pack_bits(t)), jnp.asarray(cp), jnp.asarray(lengths), impl=impl
        )
    )
    np.testing.assert_array_equal(got, np.zeros(k, np.int32))


def test_packed_zero_transaction_rows_inert():
    t, c, lengths = _random_problem(64, 48, 16, seed=5)
    want = np.asarray(ref.support_count_ref(jnp.asarray(t), jnp.asarray(c), jnp.asarray(lengths)))
    t_pad = np.concatenate([t, np.zeros((40, 48), np.int8)])
    got = np.asarray(
        ops.support_count(jnp.asarray(t_pad), jnp.asarray(c), jnp.asarray(lengths), impl="packed")
    )
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------- end-to-end ----------
def test_mine_packed_matches_dense(small_db):
    """mine() with representation='packed' returns identical results to the
    dense path on the Quest synthetic DB (the acceptance-criterion check)."""
    from repro.core.apriori import AprioriConfig, mine

    dense = mine(small_db, AprioriConfig(min_support=0.05, max_k=4, count_impl="jnp"))
    packed = mine(
        small_db,
        AprioriConfig(min_support=0.05, max_k=4, count_impl="jnp", representation="packed"),
    )
    assert dense.as_dict() == packed.as_dict()
    assert dense.min_count == packed.min_count


def test_mine_packed_interpret_kernel_small(small_db):
    """The packed Pallas kernel body (interpret) inside the full mine loop."""
    from repro.core.apriori import AprioriConfig, mine

    db = small_db[:120]
    dense = mine(db, AprioriConfig(min_support=0.08, max_k=3, count_impl="jnp"))
    packed = mine(
        db,
        AprioriConfig(
            min_support=0.08,
            max_k=3,
            count_impl="pallas_interpret",
            representation="packed",
            candidate_pad=128,
        ),
    )
    assert dense.as_dict() == packed.as_dict()
