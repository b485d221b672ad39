"""The main path's Pallas kernels compile for TPU v5e at real widths.

Nothing runs: each test compiles for a v5e:2x2 topology that is described,
not attached (the chip's compiler ships with libtpu), so a kernel Mosaic
would refuse fails here instead of on the chip. The topology is described
inside a module-scoped fixture, never at import, and the whole check lives
in this one file so a single test worker loads libtpu.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.apriori import AprioriConfig, make_count_step
from repro.kernels import ops
from repro.serving.recommend import make_match_step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("operand_dtype", ["bf16", "int8"])
def test_dense_count_kernel_compiles(one_chip, operand_dtype):
    n, i, k = 16384, 1024, 4096

    def f(t, c, ln):
        return ops.support_count(t, c, ln, impl="pallas", operand_dtype=operand_dtype)

    text = _compiled_text(f, _sds((n, i), jnp.int8, one_chip), _sds((k, i), jnp.int8, one_chip),
                          _sds((k,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("words", [32, 4])
def test_packed_count_kernel_compiles(one_chip, words):
    n, k = 16384, 4096

    def f(t, c, ln):
        return ops.support_count_packed(t, c, ln, impl="pallas")

    text = _compiled_text(f, _sds((n, words), jnp.uint32, one_chip),
                          _sds((k, words), jnp.uint32, one_chip), _sds((k,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [1, 8, 64])
def test_rule_match_kernel_compiles(one_chip, batch):
    r, w = 8192, 32

    def f(b, a, ln, c, s):
        return ops.rule_match(b, a, ln, c, s, impl="pallas")

    text = _compiled_text(
        f, _sds((batch, w), jnp.uint32, one_chip), _sds((r, w), jnp.uint32, one_chip),
        _sds((r,), jnp.int32, one_chip), _sds((r, w), jnp.uint32, one_chip),
        _sds((r,), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("representation", ["dense", "packed"])
def test_mesh_count_step_compiles(mesh, representation):
    """The 2x2 Map/Reduce count step: the kernel per shard, then the psum
    of the counts over the data axis."""
    n, items, k = 16384, 1000, 4096
    cfg = AprioriConfig(count_impl="pallas", representation=representation,
                        data_axes=("data",), model_axis="model")
    width, dtype = (items, jnp.int8) if representation == "dense" else (32, jnp.uint32)
    step = make_count_step(mesh, cfg)
    text = step.lower(
        _sds((n, width), dtype, NamedSharding(mesh, P(("data",), None))),
        _sds((k, width), dtype, NamedSharding(mesh, P("model", None))),
        _sds((k,), jnp.int32, NamedSharding(mesh, P("model"))),
    ).compile().as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text


def test_mesh_match_step_compiles(mesh):
    """The 2x2 serving step: baskets over 'data', rules over 'model', the
    item scores summed over the rule shards."""
    b, r, w = 64, 8192, 32
    step = make_match_step(mesh, impl="pallas")
    rows = NamedSharding(mesh, P("model", None))
    col = NamedSharding(mesh, P("model"))
    text = step.lower(
        _sds((b, w), jnp.uint32, NamedSharding(mesh, P(("data",), None))),
        _sds((r, w), jnp.uint32, rows), _sds((r,), jnp.int32, col),
        _sds((r, w), jnp.uint32, rows), _sds((r,), jnp.float32, col),
    ).compile().as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
