"""Streaming Map/Reduce mining over the on-disk store (DESIGN.md §9):
chunked-count exactness properties, mine_streamed / mine_son_streamed
dict-equality with the in-memory drivers, and the mesh path."""

import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import streaming
from repro.core.apriori import AprioriConfig, mine, _count_level, make_count_step, place_db
from repro.core.son import mine_son
from repro.data import store as st
from repro.data.synthetic import QuestConfig, gen_transactions

from conftest import REPO_ROOT, random_problem, subprocess_env


def _store_from_dense(dense, path, shard_rows=64):
    return st.ingest_dense(dense, str(path), shard_rows=shard_rows)


# ------------------------------------------- chunked-count correctness -------
@pytest.mark.parametrize("rep", ["dense", "packed"])
@pytest.mark.parametrize("n,chunk_rows", [(100, 7), (96, 32), (130, 129), (60, 100), (50, 1)])
def test_streamed_counts_equal_whole_db(tmp_path, rep, n, chunk_rows):
    """Property: per-chunk device accumulation == whole-DB counts, exactly,
    for chunk sizes that divide n, don't divide n, exceed n, and degenerate
    to single rows — on both representations."""
    t, _, _ = random_problem(n, 45, 4, seed=n + chunk_rows)
    rng = np.random.default_rng(n)
    cands = np.sort(rng.choice(45, size=(23, 3), replace=True), axis=1).astype(np.int32)
    cfg = AprioriConfig(count_impl="jnp", representation=rep, candidate_pad=32)

    s = _store_from_dense(t, tmp_path / "db", shard_rows=40)
    got = streaming.count_supports_streamed(s, cands, cfg, chunk_rows=chunk_rows)

    count_step = make_count_step(None, cfg)
    want = _count_level(count_step, place_db(t, cfg, None), cands, 45, cfg, None)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("rep", ["dense", "packed"])
def test_all_padding_chunk_is_inert(rep):
    """An all-padding (all-zero) chunk folded into the accumulator must not
    change any count — the invariant that lets the final chunk zero-pad to
    the jit bucket (DESIGN.md §3/§9)."""
    t, c, lengths = random_problem(40, 64, 9, seed=3)
    cfg = AprioriConfig(count_impl="jnp", representation=rep)
    step = streaming.make_accum_count_step(None, cfg)
    if rep == "packed":
        from repro.core.itemsets import pack_bits

        t_dev = jnp.asarray(pack_bits(t))
        c_dev = jnp.asarray(pack_bits(c))
    else:
        t_dev, c_dev = jnp.asarray(t), jnp.asarray(c)
    len_dev = jnp.asarray(lengths)
    acc = step(t_dev, c_dev, len_dev, jnp.zeros(9, jnp.int32))
    acc2 = step(jnp.zeros_like(t_dev), c_dev, len_dev, acc)
    np.testing.assert_array_equal(np.asarray(acc2), np.asarray(acc))


def test_multi_pass_candidate_split(tmp_path):
    """Streamed counting with max_candidates_per_pass smaller than K streams
    the DB once per pass and still matches."""
    t, _, _ = random_problem(70, 30, 4, seed=9)
    rng = np.random.default_rng(9)
    cands = np.sort(rng.choice(30, size=(40, 2), replace=True), axis=1).astype(np.int32)
    cfg = AprioriConfig(count_impl="jnp", candidate_pad=8, max_candidates_per_pass=16)
    s = _store_from_dense(t, tmp_path / "db", shard_rows=32)
    got = streaming.count_supports_streamed(s, cands, cfg, chunk_rows=33)
    count_step = make_count_step(None, cfg)
    want = _count_level(count_step, place_db(t, cfg, None), cands, 30, cfg, None)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("observed", [False, True])
@pytest.mark.parametrize("rep", ["dense", "packed"])
@pytest.mark.parametrize("where", [{}, {"start_chunk": 2}, {"shards": (1, 3)}])
def test_observed_producer_yields_the_same_chunks(tmp_path, rep, where, observed):
    """The producer reads the store's packed words and unpacks them for the
    dense step as a step of its own: with or without a MiningObs its chunks
    are byte-identical to the store's, unpacked, for every representation,
    cursor and shard range."""
    from repro.core.itemsets import unpack_bits
    from repro.obs import MetricsRegistry, MiningObs

    t, _, _ = random_problem(130, 45, 4, seed=4)
    s = _store_from_dense(t, tmp_path / "db", shard_rows=40)
    cfg = AprioriConfig(count_impl="jnp", representation=rep)
    want = [c if rep == "packed" else unpack_bits(c, 45)
            for c, _ in s.iter_chunks(17, pad=True, **where)]
    obs = MiningObs(registry=MetricsRegistry()) if observed else None
    got = list(streaming._chunks(s, 17, cfg, obs, **where))
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


# --------------------------------------------------- end-to-end equality -----
@pytest.mark.parametrize("rep", ["dense", "packed"])
def test_mine_streamed_matches_mine(tmp_path, small_db, rep):
    """The acceptance criterion: mine_streamed dict-equal to mine, both
    representations, chunk size not dividing n (300)."""
    cfg = AprioriConfig(min_support=0.05, max_k=4, count_impl="jnp", representation=rep)
    want = mine(small_db, cfg)
    s = _store_from_dense(small_db, tmp_path / "db", shard_rows=90)
    got = streaming.mine_streamed(s, cfg, chunk_rows=77)
    assert got.as_dict() == want.as_dict()
    assert got.min_count == want.min_count
    assert got.num_transactions == want.num_transactions


@pytest.mark.parametrize("rep", ["dense", "packed"])
def test_mine_son_streamed_matches_in_memory(tmp_path, small_db, rep):
    cfg = AprioriConfig(min_support=0.05, max_k=4, count_impl="jnp", representation=rep)
    want = mine(small_db, cfg)
    want_son = mine_son(small_db, cfg, num_partitions=4)
    s = _store_from_dense(small_db, tmp_path / "db", shard_rows=80)
    got = streaming.mine_son_streamed(s, cfg, chunk_rows=64)
    assert got.as_dict() == want.as_dict() == want_son.as_dict()
    assert got.min_count == want.min_count


def test_son_streamed_phase2_single_disk_scan(tmp_path, small_db, monkeypatch):
    """Phase 2 must stream the store from disk exactly ONCE for the whole
    union (all levels' accumulators fold per chunk), not once per level —
    the SON two-round promise at the I/O layer."""
    cfg = AprioriConfig(min_support=0.05, max_k=4, count_impl="jnp")
    s = _store_from_dense(small_db, tmp_path / "db", shard_rows=100)
    calls = []
    orig = s.iter_chunks

    def counting_iter_chunks(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(s, "iter_chunks", counting_iter_chunks)
    got = streaming.mine_son_streamed(s, cfg, chunk_rows=64)
    assert sum(calls) == 1, "phase 2 re-scanned the store"
    assert got.as_dict() == mine(small_db, cfg).as_dict()


def test_streamed_worker_failure_raises(tmp_path, small_db, monkeypatch):
    """A shard read failure mid-stream must abort the mine, never return a
    silently undercounted result (the pipeline exception-propagation fix)."""
    cfg = AprioriConfig(min_support=0.05, max_k=3, count_impl="jnp")
    s = _store_from_dense(small_db, tmp_path / "db", shard_rows=100)
    orig = s.iter_chunks

    def flaky_iter_chunks(*a, **kw):
        yield next(iter(orig(*a, **kw)))
        raise OSError("shard read failed")

    monkeypatch.setattr(s, "iter_chunks", flaky_iter_chunks)
    with pytest.raises(OSError, match="shard read failed"):
        streaming.mine_streamed(s, cfg, chunk_rows=64)


def test_mine_streamed_checkpoint_resume(tmp_path, small_db):
    """resume_state flows through run_level_loop for the streamed driver too."""
    cfg = AprioriConfig(min_support=0.05, max_k=4, count_impl="jnp")
    s = _store_from_dense(small_db, tmp_path / "db")
    full = streaming.mine_streamed(s, cfg)
    seen = {}
    streaming.mine_streamed(
        s, cfg, checkpoint_cb=lambda k, levels: seen.update({k: dict(levels)})
    )
    assert set(seen) == set(full.levels)
    # resume from level 2: levels 1-2 taken from state, 3+ re-mined
    resume = {"levels": {k: v for k, v in full.levels.items() if k <= 2}, "next_k": 3}
    resumed = streaming.mine_streamed(s, cfg, resume_state=resume)
    assert resumed.as_dict() == full.as_dict()


def test_chunk_rows_validation(tmp_path, small_db):
    s = _store_from_dense(small_db, tmp_path / "db")
    with pytest.raises(ValueError):
        streaming.mine_streamed(s, AprioriConfig(count_impl="jnp"), chunk_rows=0)


# ----------------------------------------------------------------- mesh ------
_MESH_STREAM = textwrap.dedent(
    """
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
    import jax
    from repro.core.apriori import AprioriConfig, mine
    from repro.core.streaming import mine_son_streamed, mine_streamed
    from repro.data.store import ingest_quest
    from repro.data.synthetic import QuestConfig, gen_transactions

    qcfg = QuestConfig(num_transactions=400, num_items=64, avg_len=8, seed=13)
    single = mine(gen_transactions(qcfg),
                  AprioriConfig(min_support=0.05, max_k=4, count_impl="jnp"))
    mesh = jax.make_mesh((2, 3), ("data", "model"))
    with tempfile.TemporaryDirectory() as d:
        store = ingest_quest(qcfg, d, shard_rows=90)
        for rep in ("dense", "packed"):
            cfg = AprioriConfig(min_support=0.05, max_k=4, count_impl="jnp",
                                representation=rep, data_axes=("data",),
                                model_axis="model", candidate_pad=256)
            got = mine_streamed(store, cfg, mesh=mesh, chunk_rows=67)  # rounds to 68
            assert got.as_dict() == single.as_dict(), rep
            son = mine_son_streamed(store, cfg, mesh=mesh, chunk_rows=64)
            assert son.as_dict() == single.as_dict(), rep + " son"
    print("MESH_STREAM_OK", single.total_frequent)
    """
)


def test_mine_streamed_on_2x3_mesh():
    """Streamed mining on a (2, 3) data x model mesh (6 host devices) is
    dict-equal to the single-device in-memory mine, both representations,
    including a chunk size that does not divide the data-shard count."""
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_STREAM],
        capture_output=True,
        text=True,
        timeout=600,
        env=subprocess_env(),
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH_STREAM_OK" in proc.stdout
