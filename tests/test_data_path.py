import numpy as np
import pytest
from _hypothesis_shim import given, settings
from _hypothesis_shim import st

from repro.core.collator import RetrievalCollator
from repro.core.config import DataArguments
from repro.core.embedding_cache import EmbeddingCache
from repro.data.graph import CSRGraph, NeighborSampler, make_random_graph
from repro.data import tokenizer as tokenizer_mod
from repro.data.tokenizer import HashTokenizer


# -- tokenizer ----------------------------------------------------------------

def test_tokenizer_deterministic():
    t1, t2 = HashTokenizer(1000), HashTokenizer(1000)
    assert t1.encode("Hello World!") == t2.encode("hello world!")


def test_tokenizer_bounds():
    t = HashTokenizer(100)
    ids = t.encode("some words " * 50, max_len=16)
    assert len(ids) == 16
    assert all(3 <= i < 100 for i in ids)


def test_tokenizer_eos():
    t = HashTokenizer(100)
    assert t.encode("a b c", append_eos=True)[-1] == t.eos_id
    assert t.encode("a b c d e", max_len=3, append_eos=True)[-1] == t.eos_id


def test_tokenizer_truncate_to_empty():
    """Truncation may leave nothing: the eos re-pin must not IndexError
    on an empty id list (regression: max_len=0 / empty text)."""
    t = HashTokenizer(100)
    assert t.encode("a b c", max_len=0, append_eos=True) == []
    assert t.encode("a b c", max_len=0) == []
    assert t.encode("", max_len=5, append_eos=True) == [t.eos_id]
    assert t.encode("", max_len=0, append_eos=True) == []
    assert t.encode("a b c", max_len=1, append_eos=True) == [t.eos_id]
    assert t.batch_encode_ids(["a b", ""], max_len=0, append_eos=True) \
        == [[], []]


def test_batch_encode_ids_matches_scalar_encode(monkeypatch):
    """The batch path must reproduce the scalar encode() exactly (same
    ids, same truncation/eos semantics), with a warm, a cold and a full
    id cache."""
    t = HashTokenizer(512)
    texts = ["Hello, World!", "", "a a a a a", "punct...!?", "x" * 40,
             " ".join(f"tok{i}" for i in range(30)), "ümlaut çedilla",
             "123 456 123"]
    for max_len in (None, 0, 3, 16):
        for eos in (False, True):
            fresh = HashTokenizer(512)     # no warm id cache
            want = [t.encode(x, max_len, eos) for x in texts]
            assert t.batch_encode_ids(texts, max_len, eos) == want
            assert fresh.batch_encode_ids(texts, max_len, eos) == want

    # a cache at its cap stores nothing more: every distinct token of
    # the batch is still hashed exactly once, through the per-call memo
    monkeypatch.setattr(tokenizer_mod, "_CACHE_CAP", 4)
    for max_len in (None, 0, 3, 16):
        for eos in (False, True):
            full = HashTokenizer(50304)
            full.encode("filler0 filler1 filler2 filler3")
            assert len(full._cache) == 4
            want = [HashTokenizer(50304).encode(x, max_len, eos)
                    for x in texts]
            got = full.batch_encode_ids(texts, max_len, eos)
            assert got == want
            assert len(full._cache) == 4
            distinct = {i for row in got for i in row} - {full.eos_id}
            assert full.stats["hashed"] == len(distinct)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.text(max_size=60), max_size=6),
       st.one_of(st.none(), st.integers(0, 40)), st.booleans())
def test_batch_encode_ids_matches_encode_property(texts, max_len, eos):
    want = [HashTokenizer(300).encode(x, max_len, eos) for x in texts]
    t = HashTokenizer(300)
    assert t.batch_encode_ids(texts, max_len, eos) == want
    assert t.batch_encode_ids(texts, max_len, eos) == want      # warm


def test_batch_encode_ids_counts_tokens_and_hashes():
    t = HashTokenizer(50304)
    texts = ["the cat sat on the mat", "", "The CAT, again!", "mat mat"]
    n_occ = sum(len(t.encode(x)) for x in texts)
    assert t.stats == {"tokens": 0, "hashed": 0}
    t2 = HashTokenizer(50304)
    t2.batch_encode_ids(texts)
    # the, cat, sat, on, mat, ",", again, "!"
    assert t2.stats == {"tokens": n_occ, "hashed": 8}
    t2.batch_encode_ids(texts)                     # every id cached now
    assert t2.stats == {"tokens": 2 * n_occ, "hashed": 8}
    # tokens past the cut are never mapped
    t3 = HashTokenizer(50304)
    t3.batch_encode_ids(texts, max_len=2)
    assert t3.stats["tokens"] == sum(min(len(t.encode(x)), 2)
                                     for x in texts)


def test_batch_encode_matches_legacy_padding():
    t = HashTokenizer(256)
    texts = ["a b c", "a", "d e f g h i j"]
    toks, mask = t.batch_encode(texts, max_len=16, pad_to_multiple=4)
    assert toks.shape == (3, 8)            # longest=7 -> padded to 8
    assert mask.sum(1).tolist() == [3, 1, 7]
    assert (toks[mask == 0] == t.pad_id).all()


@settings(max_examples=25, deadline=None)
@given(st.text(min_size=0, max_size=80), st.integers(2, 16))
def test_collator_shapes_property(text, max_len):
    args = DataArguments(query_max_len=max_len, passage_max_len=max_len,
                         vocab_size=128, pad_to_multiple=4)
    coll = RetrievalCollator(args, HashTokenizer(128))
    batch = coll([{"query": text, "passages": [text, "x"]}])
    q = batch["query"]["tokens"]
    # padded to a multiple unless capped by max_len
    assert q.shape[0] == 1
    assert q.shape[1] % 4 == 0 or q.shape[1] == max_len
    assert q.shape[1] <= max_len
    assert batch["passage"]["tokens"].shape[0] == 2
    m = batch["query"]["mask"]
    # mask is a prefix of ones
    assert (np.cumsum(1 - m[0]) * m[0] == 0).all()


def test_collator_encode_texts_per_side_budget():
    args = DataArguments(query_max_len=4, passage_max_len=16,
                         vocab_size=128, pad_to_multiple=1)
    coll = RetrievalCollator(args, HashTokenizer(128))
    text = " ".join(f"w{i}" for i in range(10))
    assert coll.max_len_for(True) == 4 and coll.max_len_for(False) == 16
    assert coll.encode_texts([text], is_query=True)["mask"].sum() == 4
    assert coll.encode_texts([text])["mask"].sum() == 10
    assert coll.encode_texts([text], max_len=2)["mask"].sum() == 2


def test_collator_labels_passthrough():
    coll = RetrievalCollator(DataArguments(vocab_size=64), HashTokenizer(64))
    batch = coll([{"query": "q", "passages": ["a", "b"],
                   "labels": np.asarray([3.0, 1.0], np.float32)}])
    assert batch["labels"].shape == (1, 2)


# -- embedding cache -----------------------------------------------------------

def test_cache_append_and_lazy_read(tmp_path, rng):
    c = EmbeddingCache(str(tmp_path / "c"), dim=8)
    v1 = rng.normal(size=(5, 8)).astype(np.float16)
    c.cache_records([f"d{i}" for i in range(5)], v1)
    v2 = rng.normal(size=(3, 8)).astype(np.float16)
    c.cache_records([f"d{i}" for i in range(5, 8)], v2)
    assert len(c) == 8
    got = c.get(["d6", "d0"])
    np.testing.assert_allclose(got[0], v2[1], rtol=1e-3)
    np.testing.assert_allclose(got[1], v1[0], rtol=1e-3)
    assert c.has(["d0", "nope"]).tolist() == [True, False]


def test_cache_reopen(tmp_path, rng):
    c = EmbeddingCache(str(tmp_path / "c"), dim=4)
    v = rng.normal(size=(3, 4)).astype(np.float16)
    c.cache_records(["a", "b", "c"], v)
    c2 = EmbeddingCache(str(tmp_path / "c"), dim=4)   # reopen from disk
    np.testing.assert_allclose(c2.get(["b"])[0], v[1], rtol=1e-3)


def test_cache_missing_raises(tmp_path):
    c = EmbeddingCache(str(tmp_path / "c"), dim=4)
    with pytest.raises(KeyError):
        c.get(["missing"])


def test_cache_missing_keyerror_names_ids(tmp_path, rng):
    """The KeyError must be actionable: it names a sample of the missing
    raw ids, not just a count."""
    c = EmbeddingCache(str(tmp_path / "c"), dim=4)
    c.cache_records(["a", "b"], rng.normal(size=(2, 4)).astype(np.float16))
    with pytest.raises(KeyError) as ei:
        c.get(["a", "ghost-1", "b", "ghost-2"])
    msg = str(ei.value)
    assert "2 ids not cached" in msg
    assert "ghost-1" in msg and "ghost-2" in msg
    assert "a" not in msg.split("(e.g.")[1].split(")")[0].split(", ")
    # more than 5 missing: sampled, with an ellipsis marker
    with pytest.raises(KeyError) as ei:
        c.get([f"ghost-{i}" for i in range(9)])
    assert "..." in str(ei.value)


def test_cache_get_rows_rejects_out_of_range(tmp_path, rng):
    """get_rows must refuse rows outside [0, n): a stale plan carrying
    -1 missing-id sentinels used to wrap via fancy indexing and silently
    serve the LAST row's embedding (regression)."""
    c = EmbeddingCache(str(tmp_path / "c"), dim=4)
    v = rng.normal(size=(3, 4)).astype(np.float16)
    c.cache_records(["a", "b", "c"], v)
    with pytest.raises(IndexError, match="stale plan"):
        c.get_rows(np.array([0, -1, 2]))
    with pytest.raises(IndexError):
        c.get_rows(np.array([3]))
    # in-range rows (and the empty request) still serve
    np.testing.assert_allclose(c.get_rows(np.array([2, 0])),
                               v[[2, 0]], rtol=1e-3)
    assert c.get_rows(np.array([], np.int64)).shape == (0, 4)


def test_cache_append_is_append_only(tmp_path, rng):
    """cache_records must write O(delta) — the ids index file grows in
    place (same inode, +8 bytes/row) instead of being re-saved in full
    on every append (the old O(n²) layout)."""
    import os
    c = EmbeddingCache(str(tmp_path / "c"), dim=4)
    ids_path = os.path.join(str(tmp_path / "c"), "ids.bin")
    c.cache_records(["a", "b"], rng.normal(size=(2, 4)).astype(np.float16))
    st1 = os.stat(ids_path)
    c.cache_records(["c"], rng.normal(size=(1, 4)).astype(np.float16))
    st2 = os.stat(ids_path)
    assert st1.st_size == 2 * 8 and st2.st_size == 3 * 8
    assert st1.st_ino == st2.st_ino        # appended, not replaced


def test_cache_reopen_after_append(tmp_path, rng):
    """Append → reopen → append again → reopen: every committed row is
    served back, in insertion order, across sessions."""
    v1 = rng.normal(size=(3, 4)).astype(np.float16)
    v2 = rng.normal(size=(2, 4)).astype(np.float16)
    c = EmbeddingCache(str(tmp_path / "c"), dim=4)
    c.cache_records(["a", "b", "c"], v1)
    c2 = EmbeddingCache(str(tmp_path / "c"), dim=4)
    c2.cache_records(["d", "e"], v2)
    assert len(c2) == 5
    c3 = EmbeddingCache(str(tmp_path / "c"), dim=4)
    got = c3.get(["e", "a", "d"])
    np.testing.assert_allclose(got[0], v2[1], rtol=1e-3)
    np.testing.assert_allclose(got[1], v1[0], rtol=1e-3)
    np.testing.assert_allclose(got[2], v2[0], rtol=1e-3)
    np.testing.assert_allclose(c3.get_range(0, 5),
                               np.concatenate([v1, v2]), rtol=1e-3)


def test_cache_ignores_torn_trailing_bytes(tmp_path, rng):
    """A crash mid-append leaves trailing bytes past the committed meta
    count; reopen must truncate them so the next append can't misalign
    the ids/vectors row mapping."""
    import os
    c = EmbeddingCache(str(tmp_path / "c"), dim=4)
    v = rng.normal(size=(2, 4)).astype(np.float16)
    c.cache_records(["a", "b"], v)
    # simulate a crash: rows hit both files but meta.json was never replaced
    with open(os.path.join(str(tmp_path / "c"), "vectors.bin"), "ab") as f:
        f.write(b"\x01" * 5)
    with open(os.path.join(str(tmp_path / "c"), "ids.bin"), "ab") as f:
        f.write(b"\x02" * 11)
    c2 = EmbeddingCache(str(tmp_path / "c"), dim=4)
    assert len(c2) == 2
    w = rng.normal(size=(1, 4)).astype(np.float16)
    c2.cache_records(["z"], w)
    c3 = EmbeddingCache(str(tmp_path / "c"), dim=4)
    np.testing.assert_allclose(c3.get(["z"])[0], w[0], rtol=1e-3)
    np.testing.assert_allclose(c3.get(["b"])[0], v[1], rtol=1e-3)


def test_cache_migrates_legacy_ids_npy(tmp_path, rng):
    """Caches written by the old layout (full ids.npy re-save per append)
    open cleanly: ids.npy is converted once to the append-only ids.bin."""
    import json as _json
    import os
    from repro.data.table import stable_id_hash_array
    d = tmp_path / "legacy"
    os.makedirs(str(d))
    v = rng.normal(size=(3, 4)).astype(np.float16)
    with open(str(d / "vectors.bin"), "wb") as f:
        f.write(v.tobytes())
    np.save(str(d / "ids.npy"), stable_id_hash_array(["a", "b", "c"]))
    with open(str(d / "meta.json"), "w") as f:
        _json.dump({"dim": 4, "dtype": "float16", "n": 3}, f)
    c = EmbeddingCache(str(d), dim=4)
    assert len(c) == 3
    np.testing.assert_allclose(c.get(["b"])[0], v[1], rtol=1e-3)
    assert os.path.exists(str(d / "ids.bin"))
    c.cache_records(["d"], rng.normal(size=(1, 4)).astype(np.float16))
    c2 = EmbeddingCache(str(d), dim=4)
    assert len(c2) == 4 and c2.has(["d"]).tolist() == [True]


# -- neighbor sampler ------------------------------------------------------------

def test_csr_from_edges():
    src = np.asarray([0, 1, 2, 0], np.int32)
    dst = np.asarray([1, 2, 0, 2], np.int32)
    g = CSRGraph.from_edges(src, dst, 3)
    assert sorted(g.neighbors(2).tolist()) == [0, 1]
    assert g.degree(np.asarray([0, 1, 2])).tolist() == [1, 1, 2]


def test_sampler_shapes_and_membership():
    src, dst, comm = make_random_graph(200, 8, seed=1)
    g = CSRGraph.from_edges(src, dst, 200)
    s = NeighborSampler(g, (5, 3), seed=0)
    l0, l1, l2 = s.sample(np.arange(10))
    assert l0.shape == (10,) and l1.shape == (10, 5) and \
        l2.shape == (10, 5, 3)
    # sampled level-1 nodes are true neighbors (or self for isolated)
    for i in range(10):
        neigh = set(g.neighbors(i).tolist()) | {i}
        assert set(l1[i].tolist()) <= neigh


def test_sampler_isolated_self_loop():
    g = CSRGraph.from_edges(np.asarray([0], np.int32),
                            np.asarray([1], np.int32), 3)
    s = NeighborSampler(g, (4,))
    _, l1 = s.sample(np.asarray([2]))
    assert (l1 == 2).all()      # node 2 has no in-edges -> self loop


def test_sample_block_features(rng):
    src, dst, _ = make_random_graph(50, 4, seed=2)
    g = CSRGraph.from_edges(src, dst, 50)
    x = rng.normal(size=(50, 6)).astype(np.float32)
    s = NeighborSampler(g, (3, 2), seed=1)
    f0, f1, f2 = s.sample_block(x, np.arange(4))
    assert f0.shape == (4, 6) and f1.shape == (4, 3, 6) and \
        f2.shape == (4, 3, 2, 6)
