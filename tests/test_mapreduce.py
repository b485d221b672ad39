"""Map/Reduce engine semantics + shard-count invariance (the paper's core
design claim: the distributed job computes exactly what a single node does)."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.mapreduce import MapReduceJob, mapreduce, pad_rows_to_shards

from conftest import REPO_ROOT, subprocess_env



def test_mapreduce_single_device_sum():
    mesh = jax.make_mesh((1,), ("data",))
    job = MapReduceJob(map_fn=lambda x: x.sum(0), reduce_axes=("data",))
    fn = mapreduce(job, mesh, in_specs=(P("data", None),))
    x = jnp.arange(12.0).reshape(4, 3)
    np.testing.assert_allclose(np.asarray(fn(x)), np.asarray(x.sum(0)))


def test_mapreduce_reduce_ops():
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.arange(8.0).reshape(4, 2)
    for op, expect in [("max", x.max(0)), ("min", x.min(0))]:
        job = MapReduceJob(map_fn=lambda v: v.max(0) if op == "max" else v.min(0), reduce_axes=("data",), reduce_op=op)
        fn = mapreduce(job, mesh, in_specs=(P("data", None),))
        np.testing.assert_allclose(np.asarray(fn(x)), np.asarray(expect))


def test_pad_rows_to_shards():
    x = np.ones((5, 3), np.int8)
    padded, n = pad_rows_to_shards(x, 4)
    assert padded.shape == (8, 3) and n == 5
    assert padded[5:].sum() == 0
    same, _ = pad_rows_to_shards(x, 5)
    assert same.shape == (5, 3)


_INVARIANCE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from repro.data.synthetic import gen_transactions, QuestConfig
    from repro.core.apriori import mine, AprioriConfig

    T = gen_transactions(QuestConfig(num_transactions=333, num_items=48, avg_len=8, seed=11))
    single = mine(T, AprioriConfig(min_support=0.06, max_k=5, count_impl="jnp"))

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    dist = mine(
        T,
        AprioriConfig(min_support=0.06, max_k=5, count_impl="jnp",
                      data_axes=("data",), model_axis="model"),
        mesh=mesh,
    )
    assert dist.as_dict() == single.as_dict(), "distributed != single-node"

    # 3-axis multi-pod style mesh, pod+data both shard rows
    mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    dist3 = mine(
        T,
        AprioriConfig(min_support=0.06, max_k=5, count_impl="jnp",
                      data_axes=("pod", "data"), model_axis="model"),
        mesh=mesh3,
    )
    assert dist3.as_dict() == single.as_dict(), "multi-pod != single-node"
    print("INVARIANCE_OK", single.total_frequent)
    """
)


def test_shard_count_invariance_multidevice():
    """Runs in a subprocess with 8 host devices: mining results are invariant
    to the mesh decomposition (1 node == 4x2 == 2x2x2)."""
    proc = subprocess.run(
        [sys.executable, "-c", _INVARIANCE_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        env=subprocess_env(),
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "INVARIANCE_OK" in proc.stdout


_PALLAS_MESH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro.core.apriori import AprioriConfig, mine
    from repro.data.synthetic import QuestConfig, gen_transactions
    from repro.launch.mesh import make_auto_mesh

    T = gen_transactions(QuestConfig(num_transactions=400, num_items=70, avg_len=6,
                                     num_patterns=8, seed=3))
    mesh = make_auto_mesh((2, 2), ("data", "model"))
    for rep in ("dense", "packed"):
        single = mine(T, AprioriConfig(min_support=0.05, max_k=3, count_impl="jnp",
                                       representation=rep))
        dist = mine(T, AprioriConfig(min_support=0.05, max_k=3,
                                     count_impl="pallas_interpret", representation=rep,
                                     data_axes=("data",), model_axis="model"), mesh=mesh)
        assert dist.as_dict() == single.as_dict(), rep
        print("PALLAS_MESH_OK", rep, single.total_frequent)
    """
)


def test_pallas_kernels_inside_mesh_mapreduce():
    """A Pallas count kernel inside the 2x2 shard_map job (4 host devices)
    mines exactly what one device does, in both representations."""
    proc = subprocess.run(
        [sys.executable, "-c", _PALLAS_MESH_SCRIPT],
        capture_output=True, text=True, timeout=600, env=subprocess_env(), cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.count("PALLAS_MESH_OK") == 2
