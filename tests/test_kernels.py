"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.core.itemsets import itemsets_to_dense, pack_bits

from conftest import random_problem as _random_problem


SHAPES = [
    (8, 16, 4),        # tiny, sub-block everything
    (100, 64, 33),     # ragged, non-multiples
    (256, 128, 128),   # exact single blocks
    (300, 130, 257),   # every dim unaligned
    (512, 512, 300),   # multi-block N and I
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("operand_dtype", ["bf16", "int8"])
def test_support_count_pallas_vs_ref(shape, operand_dtype):
    n, i, k = shape
    t, c, lengths = _random_problem(n, i, k, seed=n + i + k)
    want = np.asarray(ref.support_count_ref(jnp.asarray(t), jnp.asarray(c), jnp.asarray(lengths)))
    got = np.asarray(
        ops.support_count(
            jnp.asarray(t),
            jnp.asarray(c),
            jnp.asarray(lengths),
            impl="pallas_interpret",
            operand_dtype=operand_dtype,
            block_n=128,
            block_k=128,
            block_i=128,
        )
    )
    np.testing.assert_array_equal(got, want)  # counting is exact — no tolerance


@pytest.mark.parametrize("seed", range(3))
def test_support_count_packed_vs_dense(seed):
    t, c, lengths = _random_problem(200, 96, 50, seed=seed)
    want = np.asarray(ref.support_count_ref(jnp.asarray(t), jnp.asarray(c), jnp.asarray(lengths)))
    got = np.asarray(
        ref.support_count_packed_ref(jnp.asarray(pack_bits(t)), jnp.asarray(pack_bits(c)), block_k=32)
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("mode", ["and_cmp", "popcount"])
def test_support_count_packed_pallas_vs_ref(shape, mode):
    """Packed Pallas kernel (interpret) vs dense oracle, same shape sweep as
    the dense kernel — includes non-multiple-of-32 item counts."""
    n, i, k = shape
    t, c, lengths = _random_problem(n, i, k, seed=n + i + k)
    want = np.asarray(ref.support_count_ref(jnp.asarray(t), jnp.asarray(c), jnp.asarray(lengths)))
    got = np.asarray(
        ops.support_count_packed(
            jnp.asarray(pack_bits(t)),
            jnp.asarray(pack_bits(c)),
            jnp.asarray(lengths),
            impl="pallas_interpret",
            mode=mode,
            block_n=64,
            block_k=128,
        )
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mode", ["and_cmp", "popcount"])
def test_support_count_packed_word_tiling(mode):
    """W = 256 words (I = 8192): the word axis runs as two 128-lane slabs,
    carrying the per-pair state across slabs in the VMEM accumulator."""
    t, c, lengths = _random_problem(24, 8192, 40, seed=11, density=0.5)
    want = np.asarray(ref.support_count_ref(jnp.asarray(t), jnp.asarray(c), jnp.asarray(lengths)))
    got = np.asarray(
        ops.support_count_packed(
            jnp.asarray(pack_bits(t)), jnp.asarray(pack_bits(c)), jnp.asarray(lengths),
            impl="pallas_interpret", mode=mode,
        )
    )
    assert want.max() > 0   # some candidates are contained somewhere
    np.testing.assert_array_equal(got, want)


def test_support_count_oracle_is_right():
    """Pin the oracle itself against a hand-computed case."""
    t = np.array([[1, 1, 0, 1], [1, 0, 0, 1], [0, 1, 1, 0]], np.int8)
    cands = np.array([[0], [1], [3]], np.int32)  # singletons 0,1,3
    dense = itemsets_to_dense(cands, 4)
    got = np.asarray(ref.support_count_ref(jnp.asarray(t), jnp.asarray(dense), jnp.asarray([1, 1, 1], np.int32)))
    assert got.tolist() == [2, 2, 2]
    pair = itemsets_to_dense(np.array([[0, 3], [1, 2]], np.int32), 4)
    got = np.asarray(ref.support_count_ref(jnp.asarray(t), jnp.asarray(pair), jnp.asarray([2, 2], np.int32)))
    assert got.tolist() == [2, 1]


def test_padding_rows_never_count():
    """Padded candidates (|c| = -1) and zero-row transactions are inert."""
    t, c, lengths = _random_problem(64, 32, 16, seed=3)
    t_padded = np.concatenate([t, np.zeros((64, 32), np.int8)])
    want = np.asarray(ref.support_count_ref(jnp.asarray(t), jnp.asarray(c), jnp.asarray(lengths)))
    got = np.asarray(
        ops.support_count(
            jnp.asarray(t_padded), jnp.asarray(c), jnp.asarray(lengths), impl="pallas_interpret"
        )
    )
    np.testing.assert_array_equal(got, want)
