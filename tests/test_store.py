"""On-disk partitioned transaction store: ingest roundtrips, manifest
schema/versioning, chunk iteration and padding invariants (DESIGN.md §9)."""

import json
import os

import numpy as np
import pytest

from repro.core.itemsets import pack_bits, packed_words, unpack_bits
from repro.data import store as st
from repro.data.synthetic import QuestConfig, gen_transactions


def _rand_dense(n, i, seed=0, density=0.25):
    rng = np.random.default_rng(seed)
    return (rng.random((n, i)) < density).astype(np.int8)


# -------------------------------------------------------------- roundtrip ----
@pytest.mark.parametrize("n,i,shard_rows", [(100, 37, 30), (64, 32, 64), (257, 65, 100), (10, 7, 1000)])
def test_ingest_dense_roundtrip(tmp_path, n, i, shard_rows):
    dense = _rand_dense(n, i, seed=n)
    s = st.ingest_dense(dense, str(tmp_path / "db"), shard_rows=shard_rows)
    assert s.num_transactions == n and s.num_items == i
    assert sum(s.manifest.shard_rows) == n
    # fixed-row shards: all but the last are exactly shard_rows
    assert all(r == shard_rows for r in s.manifest.shard_rows[:-1])
    assert np.array_equal(s.read_dense(), dense)


def test_ingest_lists_matches_dense(tmp_path):
    dense = _rand_dense(50, 40, seed=2)
    lists = [np.flatnonzero(r).tolist() for r in dense]
    s1 = st.ingest_lists(lists, 40, str(tmp_path / "a"), shard_rows=16, chunk_rows=7)
    s2 = st.ingest_dense(dense, str(tmp_path / "b"), shard_rows=16)
    assert np.array_equal(s1.read_dense(), s2.read_dense())


def test_ingest_chunks_accepts_dense_and_packed(tmp_path):
    dense = _rand_dense(45, 33, seed=3)
    chunks_dense = [dense[:20], dense[20:]]
    chunks_packed = [pack_bits(dense[:10]), pack_bits(dense[10:])]
    s1 = st.ingest_chunks(chunks_dense, 33, str(tmp_path / "a"), shard_rows=16)
    s2 = st.ingest_chunks(chunks_packed, 33, str(tmp_path / "b"), shard_rows=16)
    assert np.array_equal(s1.read_dense(), dense)
    assert np.array_equal(s2.read_dense(), dense)


def test_ingest_quest_matches_gen_transactions(tmp_path):
    qcfg = QuestConfig(num_transactions=300, num_items=48, avg_len=7, seed=11)
    s = st.ingest_quest(qcfg, str(tmp_path / "q"), shard_rows=77, chunk_rows=41)
    assert np.array_equal(s.read_dense(), gen_transactions(qcfg))


# --------------------------------------------------------------- manifest ----
def test_manifest_schema_and_mmap(tmp_path):
    dense = _rand_dense(80, 70, seed=4)
    s = st.ingest_dense(dense, str(tmp_path / "db"), shard_rows=32)
    with open(os.path.join(s.path, st.MANIFEST_NAME)) as f:
        d = json.load(f)
    assert d["version"] == st.LAYOUT_VERSION
    assert d["layout"] == st.LAYOUT_NAME
    assert d["n"] == 80 and d["num_items"] == 70
    assert d["words"] == packed_words(70)
    assert d["shard_rows"] == [32, 32, 16]
    # shards open memory-mapped, packed layout
    part = s.partition_packed(0)
    assert isinstance(part, np.memmap) and part.dtype == np.uint32
    assert np.array_equal(s.partition_dense(2), dense[64:])


def test_open_store_rejects_version_mismatch(tmp_path):
    s = st.ingest_dense(_rand_dense(10, 8), str(tmp_path / "db"), shard_rows=8)
    mpath = os.path.join(s.path, st.MANIFEST_NAME)
    with open(mpath) as f:
        d = json.load(f)
    d["version"] = st.LAYOUT_VERSION + 1
    with open(mpath, "w") as f:
        json.dump(d, f)
    with pytest.raises(ValueError, match="layout version"):
        st.open_store(s.path)


def test_open_store_requires_manifest(tmp_path):
    with pytest.raises(FileNotFoundError):
        st.open_store(str(tmp_path / "nowhere"))


def test_reingest_invalidates_old_manifest_and_shards(tmp_path):
    path = str(tmp_path / "db")
    st.ingest_dense(_rand_dense(50, 8, seed=1), path, shard_rows=8)  # 7 shards
    s = st.ingest_dense(_rand_dense(12, 8, seed=2), path, shard_rows=8)
    assert s.num_transactions == 12
    assert np.array_equal(st.open_store(path).read_dense(), _rand_dense(12, 8, seed=2))
    # no orphan shard files from the larger first ingest
    shards_on_disk = sorted(f for f in os.listdir(path) if f.startswith("shard_"))
    assert shards_on_disk == [st.shard_filename(0), st.shard_filename(1)]


def test_writer_rejects_shape_mismatch(tmp_path):
    w = st.StoreWriter(str(tmp_path / "db"), num_items=16, shard_rows=8)
    with pytest.raises(ValueError):
        w.append_dense(np.zeros((4, 17), np.int8))
    with pytest.raises(ValueError):
        w.append_packed(np.zeros((4, 3), np.uint32))  # words(16) == 1


# ----------------------------------------------------------------- chunks ----
@pytest.mark.parametrize("chunk_rows", [1, 13, 30, 100, 1000])
def test_iter_chunks_covers_all_rows_across_shards(tmp_path, chunk_rows):
    dense = _rand_dense(100, 37, seed=5)
    s = st.ingest_dense(dense, str(tmp_path / "db"), shard_rows=30)
    got = []
    for chunk, valid in s.iter_chunks(chunk_rows):
        assert valid == chunk.shape[0] <= chunk_rows
        got.append(unpack_bits(chunk, 37))
    assert np.array_equal(np.concatenate(got), dense)


def test_iter_chunks_packed_matches_pack_bits(tmp_path):
    dense = _rand_dense(64, 48, seed=6)
    s = st.ingest_dense(dense, str(tmp_path / "db"), shard_rows=25)
    got = np.concatenate([c for c, _ in s.iter_chunks(17)])
    assert np.array_equal(got, pack_bits(dense))


def test_iter_chunks_pad_fixed_shape(tmp_path):
    """pad=True: every chunk has exactly chunk_rows rows, tail zero-filled
    (inert rows, DESIGN.md §3) — the fixed jit shape the streamer relies on."""
    dense = _rand_dense(50, 32, seed=7)
    s = st.ingest_dense(dense, str(tmp_path / "db"), shard_rows=20)
    chunks = list(s.iter_chunks(16, pad=True))
    assert [c.shape[0] for c, _ in chunks] == [16, 16, 16, 16]
    assert [v for _, v in chunks] == [16, 16, 16, 2]
    last, valid = chunks[-1]
    assert np.array_equal(last[valid:], np.zeros((14, last.shape[1]), np.uint32))
    assert np.array_equal(
        np.concatenate([c[:v] for c, v in chunks]), pack_bits(dense)
    )


def test_iter_chunks_rejects_bad_args(tmp_path):
    s = st.ingest_dense(_rand_dense(10, 8), str(tmp_path / "db"))
    with pytest.raises(ValueError):
        list(s.iter_chunks(0))
    with pytest.raises(ValueError):
        list(s.iter_chunks(4, start_chunk=-1))


# ------------------------------------------------------- chunk cursor seek ----
@pytest.mark.parametrize("chunk_rows,shard_rows", [(13, 30), (30, 30), (7, 100), (64, 25)])
def test_iter_chunks_start_chunk_equals_skipping(tmp_path, chunk_rows, shard_rows):
    """The resume cursor: iter_chunks(start_chunk=k) yields EXACTLY the
    chunks a full iteration yields from index k on — same shapes, same
    valid counts, same bytes — for chunk sizes that cross shard boundaries
    both ways. This is what makes a checkpointed chunk index replayable."""
    dense = _rand_dense(100, 37, seed=8)
    s = st.ingest_dense(dense, str(tmp_path / "db"), shard_rows=shard_rows)
    full = list(s.iter_chunks(chunk_rows, pad=True))
    for k in range(len(full) + 1):
        tail = list(s.iter_chunks(chunk_rows, pad=True, start_chunk=k))
        assert len(tail) == len(full) - k
        for (want, wv), (got, gv) in zip(full[k:], tail):
            assert wv == gv
            assert np.array_equal(want, got)


def test_iter_chunks_start_chunk_past_end_is_empty(tmp_path):
    s = st.ingest_dense(_rand_dense(20, 8), str(tmp_path / "db"), shard_rows=8)
    assert list(s.iter_chunks(8, start_chunk=100)) == []


# ----------------------------------------------------------- checkpoint dir ----
def test_manifest_checkpoint_dir_and_backward_compat(tmp_path):
    """New stores record a checkpoint_dir; manifests written BEFORE the
    fault-tolerance layer (no key) still open, defaulting it."""
    s = st.ingest_dense(_rand_dense(10, 8), str(tmp_path / "db"), shard_rows=8)
    assert s.checkpoint_path == os.path.join(s.path, st.DEFAULT_CHECKPOINT_DIR)
    mpath = os.path.join(s.path, st.MANIFEST_NAME)
    with open(mpath) as f:
        d = json.load(f)
    assert d["checkpoint_dir"] == st.DEFAULT_CHECKPOINT_DIR
    del d["checkpoint_dir"]                 # a pre-§11 manifest
    with open(mpath, "w") as f:
        json.dump(d, f)
    old = st.open_store(s.path)
    assert old.checkpoint_path == os.path.join(s.path, st.DEFAULT_CHECKPOINT_DIR)


# ------------------------------------------------------------- append mode ----
def test_open_for_append_roundtrip(tmp_path):
    """Append equals ingesting the concatenation: same logical rows, and the
    base shard files are never rewritten."""
    base = _rand_dense(100, 24, seed=1)
    extra = _rand_dense(37, 24, seed=2)
    p = str(tmp_path / "db")
    s0 = st.ingest_dense(base, p, shard_rows=32)
    base_shards = s0.num_partitions
    mtimes = {i: os.path.getmtime(s0.shard_path(i)) for i in range(base_shards)}
    w = st.StoreWriter.open_for_append(p)
    w.append_dense(extra)
    s1 = w.close()
    assert s1.num_transactions == 137
    assert np.array_equal(s1.read_dense(), np.concatenate([base, extra]))
    # appended rows start a NEW shard: the base prefix is untouched
    assert s1.manifest.shard_rows[:base_shards] == s0.manifest.shard_rows
    assert {i: os.path.getmtime(s1.shard_path(i)) for i in range(base_shards)} == mtimes
    # manifest generation bumped, atomically (no temp file left behind)
    assert s1.manifest.seq == s0.manifest.seq + 1
    assert not os.path.exists(os.path.join(p, st.MANIFEST_NAME + ".tmp"))


def test_append_chunks_matches_writer(tmp_path):
    base = _rand_dense(64, 16, seed=3)
    extra = _rand_dense(50, 16, seed=4)
    pa, pb = str(tmp_path / "a"), str(tmp_path / "b")
    st.ingest_dense(base, pa, shard_rows=16)
    sa = st.append_chunks([extra[:20], pack_bits(extra[20:])], pa)
    sb = st.ingest_dense(np.concatenate([base, extra]), pb, shard_rows=16)
    assert np.array_equal(sa.read_dense(), sb.read_dense())


def test_torn_append_leaves_old_manifest_readable(tmp_path):
    """Kill between shard write and manifest write: the old store must stay
    fully readable, and the next append open sweeps the orphan shards."""
    base = _rand_dense(80, 16, seed=5)
    p = str(tmp_path / "db")
    s0 = st.ingest_dense(base, p, shard_rows=32)
    w = st.StoreWriter.open_for_append(p)
    w.append_dense(_rand_dense(64, 16, seed=6))
    w._flush()                       # orphan shard files hit the disk...
    orphan = os.path.join(p, st.shard_filename(s0.num_partitions))
    assert os.path.exists(orphan)
    del w                            # ...but close() never ran: torn append
    old = st.open_store(p)
    assert old.manifest.seq == s0.manifest.seq
    assert old.num_transactions == 80
    assert np.array_equal(old.read_dense(), base)
    # recovery: a fresh append open removes the orphans and appends cleanly
    w2 = st.StoreWriter.open_for_append(p)
    assert not os.path.exists(orphan)
    extra = _rand_dense(10, 16, seed=7)
    w2.append_dense(extra)
    s2 = w2.close()
    assert np.array_equal(s2.read_dense(), np.concatenate([base, extra]))


def test_open_for_append_rejects_shape_mismatch_and_missing(tmp_path):
    with pytest.raises(FileNotFoundError):
        st.StoreWriter.open_for_append(str(tmp_path / "nope"))
    p = str(tmp_path / "db")
    st.ingest_dense(_rand_dense(10, 16, seed=8), p, shard_rows=8)
    w = st.StoreWriter.open_for_append(p)
    with pytest.raises(ValueError):
        w.append_dense(_rand_dense(4, 17, seed=9))   # wrong num_items


def test_append_preserves_count_cache_section(tmp_path):
    p = str(tmp_path / "db")
    s0 = st.ingest_dense(_rand_dense(40, 16, seed=10), p, shard_rows=16)
    meta = {"version": 1, "seq": 1, "file": "count_cache_00000001.npz",
            "min_support": 0.1, "max_k": 3, "n": 40,
            "store": {"shard_rows": list(s0.manifest.shard_rows)}, "levels": []}
    np.savez(os.path.join(p, meta["file"]))
    s0.set_count_cache(meta)
    assert st.open_store(p).count_cache_meta == meta
    s1 = st.append_chunks([_rand_dense(8, 16, seed=11)], p)
    assert s1.count_cache_meta == meta   # appends keep the section verbatim
    # clearing drops the section AND the sidecar
    s1.set_count_cache(None)
    assert st.open_store(p).count_cache_meta is None
    assert not os.path.exists(os.path.join(p, meta["file"]))


def test_iter_chunks_shard_range(tmp_path):
    dense = _rand_dense(100, 16, seed=12)
    p = str(tmp_path / "db")
    s = st.ingest_dense(dense, p, shard_rows=17)
    rows = s.manifest.shard_rows
    for s0, s1 in [(0, 2), (2, 5), (0, s.num_partitions), (3, 3)]:
        got = [unpack_bits(c, 16) for c, v in s.iter_chunks(7, shards=(s0, s1))]
        lo = sum(rows[:s0]); hi = lo + sum(rows[s0:s1])
        want = dense[lo:hi]
        assert np.array_equal(np.concatenate(got) if got else np.zeros((0, 16)), want)
    with pytest.raises(ValueError):
        list(s.iter_chunks(7, shards=(3, 2)))
    with pytest.raises(ValueError):
        list(s.iter_chunks(7, shards=(0, s.num_partitions + 1)))
