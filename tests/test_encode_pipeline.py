"""Bucketed encode pipeline: ladder geometry, order restoration, the
compile bound, and pipeline-vs-legacy ranking equivalence across the
score_impl x heap_impl x W matrix (ISSUE 5 acceptance)."""

import numpy as np
import pytest

from repro.core.collator import RetrievalCollator
from repro.core.config import DataArguments, EvaluationArguments
from repro.core.encode_pipeline import (EncodePipeline, PipelineChunkSource,
                                        bucket_ladder)
from repro.core.evaluator import RetrievalEvaluator
from repro.core.sharded_search import ShardedSearchDriver
from repro.data.tokenizer import HashTokenizer
from repro.launch.distributed import SimulatedCluster

SCORE_IMPLS = ("numpy", "jax", "pallas_fused")
HEAP_IMPLS = ("jax", "python", "pallas")


# -- ladder geometry ----------------------------------------------------------

def test_bucket_ladder_geometry():
    lad = bucket_ladder(128, n_buckets=6, multiple=8)
    assert lad[-1] == 128
    assert len(lad) <= 6
    assert all(b > a for a, b in zip(lad, lad[1:]))        # strictly up
    assert all(r % 8 == 0 for r in lad)
    assert lad[0] == 8


def test_bucket_ladder_degenerate():
    assert bucket_ladder(5, n_buckets=6, multiple=8) == (5,)
    assert bucket_ladder(64, n_buckets=1) == (64,)
    # non-multiple max_len: top rung stays exactly max_len
    assert bucket_ladder(100, n_buckets=4, multiple=8)[-1] == 100


# -- pipeline mechanics on a transparent encoder ------------------------------
#
# embedding = (sum of token ids, token count): exactly computable on the
# host, independent of padding, so order restoration and chunk/window
# alignment are checkable bit-for-bit.


def _sum_encoder():
    import jax.numpy as jnp

    def encode_fn(params, batch):
        t = batch["tokens"] * batch["mask"]
        return jnp.stack([t.sum(-1), batch["mask"].sum(-1)],
                         -1).astype(jnp.float32)

    return encode_fn


def _expected_rows(tok, texts, max_len):
    rows = []
    for t in texts:
        ids = tok.encode(t, max_len)
        rows.append([float(sum(ids)), float(len(ids))])
    return np.asarray(rows, np.float32)


@pytest.fixture()
def varied_texts():
    rng = np.random.default_rng(3)
    return [" ".join(f"w{rng.integers(1000)}"
                     for _ in range(int(rng.integers(1, 60))))
            for _ in range(137)]


def test_encode_restores_original_order(varied_texts):
    tok = HashTokenizer(4096)
    pipe = EncodePipeline(_sum_encoder(), tok, buckets=5, batch_size=16,
                          tokenizer_workers=2, depth=2)
    out = pipe.encode(None, varied_texts, 48)
    np.testing.assert_array_equal(out,
                                  _expected_rows(tok, varied_texts, 48))
    assert pipe.stats["compiles"] <= len(pipe.ladder(48))
    # bucketing must actually cut padding vs all-max_len padding
    assert pipe.stats["tokens_padded"] < 48 * len(varied_texts)


@pytest.mark.parametrize("device", (False, True))
@pytest.mark.parametrize("depth", (0, 2))
def test_stream_chunks_cover_slice_in_order(varied_texts, depth, device):
    tok = HashTokenizer(4096)
    pipe = EncodePipeline(_sum_encoder(), tok, buckets=4, batch_size=8,
                          tokenizer_workers=2, depth=depth)
    want = _expected_rows(tok, varied_texts, 32)
    lo, hi, chunk = 5, 131, 13
    offs, got = [], []
    for off, embs in pipe.stream(None, varied_texts, lo=lo, hi=hi,
                                 chunk_size=chunk, max_len=32,
                                 device=device):
        offs.append(off)
        got.append(np.asarray(embs))
    assert offs == list(range(lo, hi, chunk))
    assert [len(g) for g in got] == \
        [min(chunk, hi - o) for o in offs]
    np.testing.assert_array_equal(np.concatenate(got), want[lo:hi])


def test_chunk_source_through_driver(varied_texts):
    """The driver consumes a PipelineChunkSource via open_slice and must
    rank exactly like a plain array loader over the same embeddings."""
    tok = HashTokenizer(4096)
    pipe = EncodePipeline(_sum_encoder(), tok, buckets=4, batch_size=8,
                          tokenizer_workers=1, depth=1)
    embs = _expected_rows(tok, varied_texts, 32)
    q = embs[:7] + 0.5
    ref = ShardedSearchDriver(score_impl="numpy", chunk_size=16).search(
        q, len(varied_texts), lambda lo, hi: embs[lo:hi], 9)
    src = PipelineChunkSource(pipe, None, varied_texts, 32)
    drv = ShardedSearchDriver(score_impl="numpy", chunk_size=16)
    vals, pos = drv.search(q, len(varied_texts), src, 9)
    np.testing.assert_array_equal(pos, ref[1])
    np.testing.assert_array_equal(vals, ref[0])


def test_tokenize_workers_match_serial(varied_texts):
    tok = HashTokenizer(4096)
    serial = EncodePipeline(_sum_encoder(), tok, tokenizer_workers=1)
    fanned = EncodePipeline(_sum_encoder(), tok, tokenizer_workers=4)
    assert fanned.tokenize(varied_texts, 24) == \
        serial.tokenize(varied_texts, 24)


# -- evaluator-level equivalence: pipeline vs legacy per-batch path -----------


@pytest.fixture(scope="module")
def eq_env(tiny_retriever, tiny_params, retrieval_data):
    coll = RetrievalCollator(DataArguments(vocab_size=257),
                             HashTokenizer(257))

    def make(buckets, score_impl="jax", heap_impl="jax", rank=0, world=1,
             gather=None, sharder=None):
        # encode_batch_size=20: ragged chunks AND a ragged bucket tail
        return RetrievalEvaluator(
            EvaluationArguments(topk=10, encode_batch_size=20,
                                score_impl=score_impl, heap_impl=heap_impl,
                                encode_buckets=buckets,
                                metrics=("ndcg@10",)),
            tiny_retriever, coll, tiny_params, process_index=rank,
            process_count=world, gather=gather, sharder=sharder)

    legacy = make(0)
    assert legacy.encode_pipeline is None
    run = legacy.search(retrieval_data["queries"], retrieval_data["corpus"])
    return {"make": make, "run": run}


@pytest.mark.parametrize("heap_impl", HEAP_IMPLS)
@pytest.mark.parametrize("score_impl", SCORE_IMPLS)
def test_pipeline_matches_legacy_matrix(eq_env, retrieval_data, score_impl,
                                        heap_impl):
    """Online regime (no cache): the bucketed pipeline must return the
    legacy per-batch path's rankings bit-for-bit for every backend."""
    ev = eq_env["make"](6, score_impl, heap_impl)
    assert ev.encode_pipeline is not None
    qh, ids, vals = ev.search(retrieval_data["queries"],
                              retrieval_data["corpus"])
    rqh, rids, rvals = eq_env["run"]
    np.testing.assert_array_equal(qh, rqh)
    np.testing.assert_array_equal(ids, rids)
    np.testing.assert_allclose(vals, rvals, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("world", (2, 4))
def test_pipeline_matches_legacy_multiworker(eq_env, retrieval_data, world):
    """W simulated workers, each streaming its shard slice through its
    own pipeline, still reproduce the legacy W=1 rankings exactly."""
    cluster = SimulatedCluster(world)
    evs = [eq_env["make"](6, "jax", "jax", rank, world, cluster.gather,
                          cluster.sharder) for rank in range(world)]
    outs = cluster.run(
        lambda rank: evs[rank].search(retrieval_data["queries"],
                                      retrieval_data["corpus"]))
    rqh, rids, rvals = eq_env["run"]
    for qh, ids, vals in outs:
        np.testing.assert_array_equal(ids, rids)
        np.testing.assert_allclose(vals, rvals, rtol=1e-5, atol=1e-6)


# -- compile-count regression -------------------------------------------------


def test_compile_count_bounded_by_ladder(tiny_retriever, tiny_params):
    """Encode a corpus of widely varying lengths: encoder compiles must
    stay <= ladder size + a small constant (query shapes), no matter how
    many distinct per-batch max lengths the corpus produces.  The legacy
    path compiles one executable per distinct padded shape — this pins
    shape churn out."""
    coll = RetrievalCollator(DataArguments(vocab_size=257),
                             HashTokenizer(257))
    ev = RetrievalEvaluator(
        EvaluationArguments(topk=5, encode_batch_size=16,
                            metrics=("ndcg@10",)),
        tiny_retriever, coll, tiny_params)
    rng = np.random.default_rng(11)
    corpus = {f"d{i}": " ".join(f"w{rng.integers(5000)}"
                                for _ in range(int(rng.integers(1, 128))))
              for i in range(160)}
    queries = {f"q{i}": f"w{i} w{i + 1} w{i + 2}" for i in range(6)}
    ev.search(queries, corpus)
    pipe = ev.encode_pipeline
    ladder = pipe.ladder(coll.args.passage_max_len)
    assert pipe.stats["compiles"] <= len(ladder) + 2
    # jax's own executable count must agree with the trace-time
    # counter — the stat is real compiles, not a proxy
    assert pipe.jit_cache_size() == pipe.stats["compiles"]
    # a second search over the same shapes must not recompile
    before = pipe.stats["compiles"]
    ev.search(queries, corpus)
    assert pipe.stats["compiles"] == before


# -- multi-node hard-negative mining write discipline -------------------------


def test_mine_hard_negatives_writes_only_on_worker0(
        tiny_retriever, tiny_params, retrieval_data, tmp_path):
    """All workers compute the identical merged triplets; only worker 0
    may write output_path (duplicate/racy writes on a shared FS)."""
    coll = RetrievalCollator(DataArguments(vocab_size=257),
                             HashTokenizer(257))
    cluster = SimulatedCluster(2)
    evs = [RetrievalEvaluator(
        EvaluationArguments(topk=8, metrics=("ndcg@10",)),
        tiny_retriever, coll, tiny_params, process_index=rank,
        process_count=2, gather=cluster.gather, sharder=cluster.sharder)
        for rank in range(2)]
    paths = [tmp_path / f"negs_rank{rank}.tsv" for rank in range(2)]
    outs = cluster.run(lambda rank: evs[rank].mine_hard_negatives(
        retrieval_data["queries"], retrieval_data["corpus"],
        retrieval_data["qrels"], depth=8, output_path=str(paths[rank])))
    assert outs[0] == outs[1]                  # allgather semantics
    assert paths[0].exists()
    assert not paths[1].exists()               # rank 1 must not write
    lines = paths[0].read_text().splitlines()
    assert len(lines) == len(outs[0])
    q, d, s = lines[0].split("\t")
    assert (q, d, float(s)) == (outs[0][0][0], outs[0][0][1],
                                pytest.approx(outs[0][0][2]))
