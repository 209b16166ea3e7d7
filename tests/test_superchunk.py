"""Superchunk scan executor: one-dispatch-per-superchunk streaming search.

The scan path (``kernels.ops.superchunk_update`` driven by
``ShardedSearchDriver._search_superchunk``) must reproduce the per-chunk
dispatch path bit for bit for every device score_impl × heap_impl combo,
across ragged tails, padded final superchunks, empty shards, and the
prefetch pipeline — while collapsing the dispatch count to
ceil(chunks / S).  The resident executor (a ``ResidentRows`` corpus read
in place by ``kernels.ops.superchunk_update``) must return the
streamed executor's state bit for bit, without ever loading a chunk.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.sharded_search import (ResidentRows, ShardedSearchDriver,
                                       autotune_superchunk_size)
from repro.kernels import ops

SCAN_SCORE_IMPLS = ("jax", "pallas_fused")
SCAN_HEAP_IMPLS = ("jax", "pallas")


@pytest.fixture()
def synth():
    rng = np.random.default_rng(11)
    q = rng.normal(size=(9, 16)).astype(np.float32)
    docs = rng.normal(size=(230, 16)).astype(np.float32)
    return q, docs


def _oracle(q, docs, k):
    full = q @ docs.T
    pos = np.argsort(-full, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(full, pos, 1), pos


class _NoLoadRows(ResidentRows):
    """A resident corpus whose legacy ``(lo, hi)`` loader must never run:
    the resident executor reads ``rows`` in place."""

    __slots__ = ()

    def __call__(self, lo, hi):
        raise AssertionError(f"resident corpus loaded as chunk [{lo}, {hi})")


class _BareRows:
    """A loader whose ``rows`` device array has no padding past the end."""

    def __init__(self, docs):
        self.rows = jnp.asarray(docs)

    def __call__(self, lo, hi):
        return self.rows[lo:hi]


def _cluster_search(q, n, load, w, k, **kw):
    """Per-rank outs and driver stats of one W-worker round."""
    from repro.launch.distributed import SimulatedCluster
    if w == 1:
        d = ShardedSearchDriver(**kw)
        return [d.search(q, n, load, k)], [d.stats]
    cluster = SimulatedCluster(w)
    drivers = [ShardedSearchDriver(
        n_workers=w, worker_index=rank, sharder=cluster.sharder,
        gather=cluster.gather, **kw) for rank in range(w)]
    outs = cluster.run(lambda rank: drivers[rank].search(q, n, load, k))
    return outs, [d.stats for d in drivers]


@pytest.mark.parametrize("heap_impl", SCAN_HEAP_IMPLS)
@pytest.mark.parametrize("score_impl", SCAN_SCORE_IMPLS)
def test_scan_matches_oracle(synth, score_impl, heap_impl):
    """chunk=37 leaves a ragged tail; S=3 leaves a padded final group."""
    q, docs = synth
    driver = ShardedSearchDriver(score_impl=score_impl,
                                 heap_impl=heap_impl, chunk_size=37,
                                 superchunk_size=3)
    vals, pos = driver.search(q, docs.shape[0],
                              lambda lo, hi: docs[lo:hi], 10)
    ref_vals, ref_pos = _oracle(q, docs, 10)
    assert driver.stats["executor"] == "superchunk"
    np.testing.assert_array_equal(pos, ref_pos)
    np.testing.assert_allclose(vals, ref_vals, rtol=1e-5)


@pytest.mark.parametrize("heap_impl", SCAN_HEAP_IMPLS)
@pytest.mark.parametrize("score_impl", SCAN_SCORE_IMPLS)
def test_scan_bitwise_equals_per_chunk(synth, score_impl, heap_impl):
    """superchunk_size=1 is the pre-superchunk per-chunk dispatch path;
    the scan must return the identical (ids bitwise) ranking."""
    q, docs = synth
    outs = {}
    for s in (1, 4):
        d = ShardedSearchDriver(score_impl=score_impl,
                                heap_impl=heap_impl, chunk_size=23,
                                superchunk_size=s)
        outs[s] = d.search(q, docs.shape[0],
                           lambda lo, hi: docs[lo:hi], 7)
        assert d.stats["executor"] == ("per_chunk" if s == 1
                                       else "superchunk")
    np.testing.assert_array_equal(outs[1][1], outs[4][1])
    np.testing.assert_allclose(outs[1][0], outs[4][0], rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("w", (1, 2, 3))
@pytest.mark.parametrize("heap_impl", SCAN_HEAP_IMPLS)
@pytest.mark.parametrize("score_impl", SCAN_SCORE_IMPLS)
def test_resident_scan_bitwise_equals_streamed_and_oracle(
        synth, score_impl, heap_impl, w):
    """230 rows in chunks of 37 leave a ragged tail, S=3 a padded final
    group, and W=2/3 shard bounds off the chunk grid: every rank's
    resident round equals the streamed round bit for bit (scores too:
    the same step body sees the same rows) and the oracle's ranking."""
    q, docs = synth
    kw = dict(score_impl=score_impl, heap_impl=heap_impl, chunk_size=37,
              superchunk_size=3)
    streamed, s_stats = _cluster_search(q, docs.shape[0],
                                        lambda lo, hi: docs[lo:hi], w, 10,
                                        **kw)
    resident, r_stats = _cluster_search(q, docs.shape[0],
                                        _NoLoadRows(docs, 37), w, 10, **kw)
    _, ref_pos = _oracle(q, docs, 10)
    assert {st["executor"] for st in s_stats} == {"superchunk"}
    assert {st["executor"] for st in r_stats} == {"resident"}
    if w > 1:
        assert any(st["lo"] % 37 for st in r_stats)   # off the chunk grid
    for (s_vals, s_pos), (r_vals, r_pos) in zip(streamed, resident):
        np.testing.assert_array_equal(r_pos, s_pos)
        np.testing.assert_array_equal(r_vals, s_vals)
        np.testing.assert_array_equal(r_pos, ref_pos)


@pytest.mark.parametrize("s", (3, 4, 8))
def test_resident_dispatch_counts(synth, s):
    """ceil(230/32) = 8 chunks fold into ceil(8/S) in-place scans, with
    the round's counters those of a streamed round."""
    q, docs = synth
    d = ShardedSearchDriver(score_impl="jax", chunk_size=32,
                            superchunk_size=s)
    d.search(q, docs.shape[0], _NoLoadRows(docs, 32), 5)
    assert d.stats["executor"] == "resident"
    assert d.stats["chunks"] == 8
    assert d.stats["dispatch_rounds"] == -(-8 // s)
    assert d.stats["superchunk_size"] == s
    assert (d.stats["lo"], d.stats["hi"], d.stats["items"]) == (0, 230, 230)


def test_resident_rounds_share_one_executable(synth):
    """Chunk offsets ride the scan xs: rounds over different ``[lo, hi)``
    (moving shard bounds, unaligned to the chunk grid) reuse the resident
    scan's compiled executable."""
    q, docs = synth
    src = _NoLoadRows(docs, 32)
    d = ShardedSearchDriver(score_impl="jax", chunk_size=32,
                            superchunk_size=4)
    d._score_range(q, 0, 230, src, 5, 0)
    before = ops._superchunk_scan_resident_jit._cache_size()
    for round_no, (lo, hi) in enumerate(((7, 199), (101, 230), (0, 33))):
        heap, _, executor, _ = d._score_range(q, lo, hi, src, 5,
                                              round_no + 1)
        assert executor == "resident"
        _, ref_pos = _oracle(q, docs[lo:hi], 5)
        np.testing.assert_array_equal(heap.finalize()[1], ref_pos + lo)
    assert ops._superchunk_scan_resident_jit._cache_size() == before


def test_resident_needs_room_for_the_last_read(synth):
    """A device array without the padding a fixed-size read needs past
    the last chunk start is streamed, never read out of bounds (a
    clamped dynamic_slice would score the wrong rows)."""
    q, docs = synth
    bare = _BareRows(docs)
    d = ShardedSearchDriver(score_impl="jax", chunk_size=32,
                            superchunk_size=4)
    _, pos = d.search(q, docs.shape[0], bare, 5)
    assert d.stats["executor"] == "superchunk"
    np.testing.assert_array_equal(pos, _oracle(q, docs, 5)[1])
    with pytest.raises(ValueError, match="runs past"):
        ops.superchunk_update(
            jnp.full((16, 5), -jnp.inf), jnp.full((16, 5), -1, jnp.int32),
            np.zeros((16, 16), np.float32), bare.rows,
            np.array([224], np.int32), np.array([6], np.int32), k=5,
            chunk_size=32)


def test_scan_dispatch_counts(synth):
    """ceil(230/32) = 8 chunks fold into ceil(8/4) = 2 scan dispatches."""
    q, docs = synth
    driver = ShardedSearchDriver(score_impl="jax", chunk_size=32,
                                 superchunk_size=4)
    driver.search(q, docs.shape[0], lambda lo, hi: docs[lo:hi], 5)
    assert driver.stats["chunks"] == 8
    assert driver.stats["dispatch_rounds"] == 2
    assert driver.stats["superchunk_size"] == 4
    per_chunk = ShardedSearchDriver(score_impl="jax", chunk_size=32,
                                    superchunk_size=1)
    per_chunk.search(q, docs.shape[0], lambda lo, hi: docs[lo:hi], 5)
    assert per_chunk.stats["dispatch_rounds"] == 8


def test_scan_with_prefetch_identical(synth):
    q, docs = synth
    outs = {}
    for prefetch in (False, True):
        d = ShardedSearchDriver(score_impl="jax", chunk_size=23,
                                superchunk_size=4, prefetch=prefetch)
        outs[prefetch] = d.search(q, docs.shape[0],
                                  lambda lo, hi: docs[lo:hi], 7)
    np.testing.assert_array_equal(outs[True][1], outs[False][1])
    np.testing.assert_array_equal(outs[True][0], outs[False][0])


def test_scan_device_resident_chunks(synth):
    """The online-encode regime hands the driver jax arrays, not numpy;
    the stacking path must keep them device-side and stay correct."""
    q, docs = synth
    d = ShardedSearchDriver(score_impl="jax", chunk_size=37,
                            superchunk_size=3)
    vals, pos = d.search(q, docs.shape[0],
                         lambda lo, hi: jnp.asarray(docs[lo:hi]), 10)
    _, ref_pos = _oracle(q, docs, 10)
    np.testing.assert_array_equal(pos, ref_pos)


def test_numpy_and_python_backends_stay_per_chunk(synth):
    q, docs = synth
    for score_impl, heap_impl in (("numpy", "jax"), ("jax", "python")):
        d = ShardedSearchDriver(score_impl=score_impl,
                                heap_impl=heap_impl, chunk_size=32,
                                superchunk_size=16)
        _, pos = d.search(q, docs.shape[0], lambda lo, hi: docs[lo:hi], 5)
        assert d.stats["executor"] == "per_chunk"
        _, ref_pos = _oracle(q, docs, 5)
        np.testing.assert_array_equal(pos, ref_pos)


def test_autotune_in_range_and_cached():
    s1 = autotune_superchunk_size(9, 16, 32, 10, "jax", "jax")
    s2 = autotune_superchunk_size(9, 16, 32, 10, "jax", "jax")
    assert 8 <= s1 <= 256
    assert s1 == s2                       # memoized per (shape, backend)


def test_memory_cap_bounds_superchunk():
    """A configured S that would blow the tile budget is clamped."""
    d = ShardedSearchDriver(score_impl="jax", chunk_size=1024,
                            superchunk_size=10_000, superchunk_max_mb=4)
    cap = (4 << 20) // (1024 * 64 * 4)
    assert d._resolve_superchunk_size(8, 64, 10) == cap


# -- zero-length corpus slices (FairSharder emits them legitimately) ----------


def test_fused_score_topk_empty_corpus():
    """n=0 must return a clean (-inf, -1) state, not a zero-size grid."""
    q = np.zeros((3, 8), np.float32)
    vals, ids = ops.fused_score_topk(q, np.zeros((0, 8), np.float32), 5)
    assert vals.shape == (3, 5) and ids.shape == (3, 5)
    assert (np.asarray(vals) == -np.inf).all()
    assert (np.asarray(ids) == -1).all()


@pytest.mark.parametrize("score_impl", SCAN_SCORE_IMPLS)
def test_empty_shards_through_driver(synth, score_impl):
    """total_items < n_workers: some shards are empty; every rank of the
    cluster must still return the W=1 ranking (regression through
    ShardedSearchDriver.search for the device backends)."""
    from repro.launch.distributed import SimulatedCluster
    q, docs = synth
    docs = docs[:3]
    single = ShardedSearchDriver(score_impl=score_impl, chunk_size=8)
    ref_vals, ref_pos = single.search(q, 3, lambda lo, hi: docs[lo:hi], 5)
    cluster = SimulatedCluster(4)
    drivers = [ShardedSearchDriver(
        n_workers=4, worker_index=rank, sharder=cluster.sharder,
        score_impl=score_impl, chunk_size=8, gather=cluster.gather)
        for rank in range(4)]
    outs = cluster.run(
        lambda rank: drivers[rank].search(q, 3,
                                          lambda lo, hi: docs[lo:hi], 5))
    for vals, pos in outs:
        np.testing.assert_array_equal(pos, ref_pos)
        np.testing.assert_allclose(vals, ref_vals, rtol=1e-5)
    assert (ref_pos[:, 3:] == -1).all()   # k=5 > 3 docs: clean empty tail


def test_empty_corpus_through_driver():
    d = ShardedSearchDriver(score_impl="jax", superchunk_size=4)
    vals, pos = d.search(np.zeros((2, 4), np.float32), 0,
                         lambda lo, hi: np.zeros((0, 4), np.float32), 3)
    assert (pos == -1).all() and (vals == -np.inf).all()


# -- scan-friendly kernel entries ---------------------------------------------


def test_superchunk_update_traced_offsets_no_recompile():
    """Offsets and valid counts ride the scan xs: two superchunks with
    different offsets must hit the same compiled executable."""
    rng = np.random.default_rng(0)
    q = rng.normal(size=(8, 16)).astype(np.float32)
    tile = rng.normal(size=(4, 32, 16)).astype(np.float32)
    v = jnp.full((8, 5), -jnp.inf, jnp.float32)
    i = jnp.full((8, 5), -1, jnp.int32)
    v, i = ops.superchunk_update(
        v, i, q, tile, np.arange(0, 128, 32, dtype=np.int32),
        np.full(4, 32, np.int32), k=5)
    before = ops._superchunk_scan_jit._cache_size()
    v, i = ops.superchunk_update(
        v, i, q, tile, np.arange(1000, 1128, 32, dtype=np.int32),
        np.full(4, 32, np.int32), k=5)
    assert ops._superchunk_scan_jit._cache_size() == before


def test_superchunk_update_masks_padded_steps():
    """Steps with n_valid=0 (padded final group) must contribute nothing,
    even though their zero embeddings would otherwise score 0 > -inf."""
    rng = np.random.default_rng(1)
    q = rng.normal(size=(8, 16)).astype(np.float32)
    docs = -np.abs(rng.normal(size=(32, 16))).astype(np.float32)
    tile = np.zeros((3, 32, 16), np.float32)
    tile[0] = docs
    offs = np.array([0, 0, 0], np.int32)
    nvs = np.array([32, 0, 0], np.int32)
    v = jnp.full((8, 5), -jnp.inf, jnp.float32)
    i = jnp.full((8, 5), -1, jnp.int32)
    v, i = ops.superchunk_update(v, i, q, tile, offs, nvs, k=5)
    _, ref_pos = _oracle_like(q, docs, 5)
    np.testing.assert_array_equal(np.asarray(i), ref_pos)


def _oracle_like(q, docs, k):
    full = q @ docs.T
    pos = np.argsort(-full, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(full, pos, 1), pos
