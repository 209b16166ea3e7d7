"""Continuous-batching serve frontend (core.serving + launch.serve).

Pins the contract of ROADMAP item 1: concurrent submitters through the
micro-batching frontend get results identical to solo
``RetrievalEvaluator.search`` calls per query (ids bitwise, scores
allclose — the repo's cross-impl convention) across the ``score_impl``
× W ∈ {1, 2} matrix; the deadline flush fires for a lone queued query;
admission control never drops an accepted request; shutdown drains the
queue; and ``launch.serve`` measures steady-state latencies (the old
warm-up lie) over exactly-``--batch``-query requests (the old
truncating slice).
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core.collator import RetrievalCollator
from repro.core.config import DataArguments, EvaluationArguments
from repro.core.embedding_cache import EmbeddingCache
from repro.core.evaluator import RetrievalEvaluator
from repro.core.serving import (ClusterServeBackend, EvaluatorServeBackend,
                                ServeClosedError, ServeFrontend,
                                ServeOverloadError)
from repro.core.sharded_search import ShardedSearchDriver
from repro.data.table import stable_id_hash
from repro.data.tokenizer import HashTokenizer
from repro.launch.distributed import SimulatedCluster

pytestmark = pytest.mark.serving


# -- frontend mechanics (trivial callable backend, no encoder) ----------------


def _echo_backend(delay=0.0):
    """Backend whose ids encode (query index within batch) — demux order
    is checkable without a model.  Texts are 'q<i>' strings."""

    def run(texts, topk):
        if delay:
            time.sleep(delay)
        qnum = np.asarray([int(t[1:]) for t in texts])
        ids = qnum[:, None] * 100 + np.arange(topk)[None, :]
        return ids, ids.astype(np.float32)

    return run


def test_demux_routes_rows_to_the_right_request():
    with ServeFrontend(_echo_backend(), topk=3, max_batch=8,
                       max_wait_ms=20) as fe:
        futs = {i: fe.submit(f"q{i}") for i in range(20)}
        for i, f in futs.items():
            ids, vals = f.result(timeout=10)
            assert ids.shape == (1, 3)
            np.testing.assert_array_equal(ids[0], i * 100 + np.arange(3))
    assert fe.stats["completed"] == 20
    assert fe.stats["queries"] == 20            # pad rows not counted


def test_small_batch_requests_coalesce_and_demux():
    with ServeFrontend(_echo_backend(), topk=2, max_batch=8,
                       max_wait_ms=20) as fe:
        f1 = fe.submit(["q3", "q5", "q7"])
        f2 = fe.submit("q9")
        f3 = fe.submit({"a": "q1", "b": "q2"})
        ids1, _ = f1.result(10)
        assert ids1.shape == (3, 2)
        np.testing.assert_array_equal(ids1[:, 0], [300, 500, 700])
        np.testing.assert_array_equal(f2.result(10)[0][:, 0], [900])
        np.testing.assert_array_equal(f3.result(10)[0][:, 0], [100, 200])


def test_deadline_flush_fires_for_a_single_queued_query():
    """A lone query must not wait for max_batch company: the deadline
    flushes it after max_wait_ms."""
    with ServeFrontend(_echo_backend(), topk=2, max_batch=64,
                       max_wait_ms=30) as fe:
        t0 = time.monotonic()
        ids, _ = fe.submit("q4").result(timeout=10)
        dt = time.monotonic() - t0
        np.testing.assert_array_equal(ids[0], [400, 401])
    assert fe.stats["flush_deadline"] == 1
    assert fe.stats["batches"] == 1
    assert dt < 5.0                      # deadline, not forever


def test_full_flush_does_not_wait_for_deadline():
    """max_batch queries queued -> flush immediately (reason 'full'),
    far before a long deadline."""
    with ServeFrontend(_echo_backend(), topk=2, max_batch=4,
                       max_wait_ms=10_000) as fe:
        futs = [fe.submit(f"q{i}") for i in range(4)]
        t0 = time.monotonic()
        for f in futs:
            f.result(timeout=10)
        assert time.monotonic() - t0 < 5.0
    assert fe.stats["flush_full"] >= 1


def test_oversized_batch_splits_on_request_boundary():
    """A request that would overflow the forming micro-batch is carried
    whole into the next one — requests are never split."""
    with ServeFrontend(_echo_backend(), topk=2, max_batch=4,
                       max_wait_ms=10) as fe:
        futs = [fe.submit(["q1", "q2", "q3"]),
                fe.submit(["q4", "q5", "q6"]),
                fe.submit(["q7", "q8"])]
        for f in futs:
            f.result(timeout=10)
        assert fe.stats["queries"] == 8
        assert fe.stats["max_batch_seen"] <= 4


def test_overload_rejects_fast_but_never_drops_accepted():
    accepted, rejected = [], []
    lock = threading.Lock()
    fe = ServeFrontend(_echo_backend(delay=0.02), topk=2, max_batch=1,
                       max_wait_ms=0, max_queue=2)

    def client(i):
        try:
            f = fe.submit(f"q{i}")
        except ServeOverloadError:
            with lock:
                rejected.append(i)
            return
        with lock:
            accepted.append((i, f))

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(client, range(24)))
    fe.close()
    assert rejected, "overload never triggered — queue bound not enforced"
    assert accepted, "every request rejected"
    # every accepted request resolved with its own correct rows
    for i, f in accepted:
        ids, _ = f.result(timeout=0)     # must already be done post-close
        np.testing.assert_array_equal(ids[0], [i * 100, i * 100 + 1])
    assert fe.stats["accepted"] == len(accepted) == fe.stats["completed"]
    assert fe.stats["rejected"] == len(rejected)


def test_close_drains_queue_then_refuses_new_requests():
    fe = ServeFrontend(_echo_backend(delay=0.01), topk=2, max_batch=2,
                       max_wait_ms=0, max_queue=64)
    futs = [fe.submit(f"q{i}") for i in range(10)]
    fe.close()                           # must drain all 10, then stop
    for i, f in enumerate(futs):
        ids, _ = f.result(timeout=0)
        assert ids[0][0] == i * 100
    assert fe.stats["completed"] == 10
    with pytest.raises(ServeClosedError):
        fe.submit("q0")
    fe.close()                           # idempotent


def test_backend_error_propagates_to_every_request_future():
    def boom(texts, topk):
        raise RuntimeError("backend down")

    with ServeFrontend(boom, topk=2, max_batch=4, max_wait_ms=5) as fe:
        futs = [fe.submit(f"q{i}") for i in range(3)]
        for f in futs:
            with pytest.raises(RuntimeError, match="backend down"):
                f.result(timeout=10)
    assert fe.stats["failed"] == 3


# -- construction-time validation ---------------------------------------------


@pytest.mark.parametrize("kwargs", (
    {"topk": 0}, {"topk": -3}, {"max_batch": 0}, {"max_wait_ms": -1.0},
    {"max_queue": 0},
))
def test_frontend_rejects_bad_knobs(kwargs):
    with pytest.raises(ValueError):
        ServeFrontend(_echo_backend(), **kwargs)


def test_frontend_rejects_backend_without_entry_point():
    with pytest.raises(ValueError, match="backend"):
        ServeFrontend(object())


@pytest.mark.parametrize("kwargs", (
    {"topk": 0}, {"topk": -1}, {"serve_max_batch": 0},
    {"serve_max_wait_ms": -0.5}, {"serve_max_queue": 0},
    {"score_impl": "torch"}, {"heap_impl": "cuda"},
    {"encode_batch_size": 0}, {"superchunk_max_mb": 0},
))
def test_evaluation_arguments_reject_bad_knobs(kwargs):
    with pytest.raises(ValueError):
        EvaluationArguments(**kwargs)


def test_evaluation_arguments_error_names_the_knob():
    with pytest.raises(ValueError, match="score_impl"):
        EvaluationArguments(score_impl="torch")
    with pytest.raises(ValueError, match="topk"):
        EvaluationArguments(topk=0)


def test_result_heap_rejects_unknown_impl_and_bad_k():
    from repro.core.result_heap import FastResultHeapq
    with pytest.raises(ValueError, match="impl"):
        FastResultHeapq(4, 3, impl="torch")
    with pytest.raises(ValueError, match="k must"):
        FastResultHeapq(4, 0)


def test_empty_and_oversized_requests_rejected_at_submit():
    with ServeFrontend(_echo_backend(), topk=2, max_batch=4,
                       max_wait_ms=0) as fe:
        with pytest.raises(ValueError, match="empty"):
            fe.submit([])
        with pytest.raises(ValueError, match="exceeds max_batch"):
            fe.submit([f"q{i}" for i in range(5)])


# -- driver async reduce ------------------------------------------------------


def test_search_async_matches_sync_over_pipelined_rounds():
    rng = np.random.default_rng(3)
    q = rng.normal(size=(6, 16)).astype(np.float32)
    docs = rng.normal(size=(150, 16)).astype(np.float32)
    load = lambda lo, hi: docs[lo:hi]
    sync = ShardedSearchDriver(score_impl="numpy", chunk_size=40)
    ref = sync.search(q, 150, load, 7)
    drv = ShardedSearchDriver(score_impl="numpy", chunk_size=40)
    futs = [drv.search_async(q, 150, load, 7) for _ in range(4)]
    for f in futs:                       # rounds overlap reduce w/ score
        vals, pos = f.result(timeout=30)
        np.testing.assert_array_equal(pos, ref[1])
        np.testing.assert_allclose(vals, ref[0], rtol=1e-6)
    drv.close()
    drv.close()                          # idempotent


def test_search_async_matches_sync_across_cluster_rounds():
    """W=2 drivers each running R pipelined rounds: round r's gather
    merge (on the reduce thread) overlaps round r+1's scoring, and every
    round still reproduces the sync result on every rank."""
    rng = np.random.default_rng(5)
    q = rng.normal(size=(5, 16)).astype(np.float32)
    docs = rng.normal(size=(130, 16)).astype(np.float32)
    load = lambda lo, hi: docs[lo:hi]
    single = ShardedSearchDriver(score_impl="numpy", chunk_size=32)
    ref_vals, ref_pos = single.search(q, 130, load, 6)
    cluster = SimulatedCluster(2)
    drivers = [ShardedSearchDriver(
        n_workers=2, worker_index=rank, sharder=cluster.sharder,
        score_impl="numpy", chunk_size=32, gather=cluster.gather)
        for rank in range(2)]

    def worker(rank):
        futs = [drivers[rank].search_async(q, 130, load, 6)
                for _ in range(3)]
        return [f.result(timeout=60) for f in futs]

    outs = cluster.run(worker)
    for rank in range(2):
        drivers[rank].close()
        for vals, pos in outs[rank]:
            np.testing.assert_array_equal(pos, ref_pos)
            np.testing.assert_allclose(vals, ref_vals, rtol=1e-5,
                                       atol=1e-6)


# -- evaluator-backed frontend: the score_impl × W matrix ---------------------


@pytest.fixture(scope="module")
def serve_env(tiny_retriever, tiny_params, retrieval_data,
              tmp_path_factory):
    """Solo per-query reference runs + a shared warm cache."""
    coll = RetrievalCollator(DataArguments(vocab_size=257),
                             HashTokenizer(257))
    cache = EmbeddingCache(str(tmp_path_factory.mktemp("svcache") / "c"),
                           dim=32)

    def make(score_impl, rank=0, world=1, gather=None, sharder=None):
        return RetrievalEvaluator(
            EvaluationArguments(topk=5, encode_batch_size=20,
                                score_impl=score_impl,
                                serve_max_batch=8, serve_max_wait_ms=4.0),
            tiny_retriever, coll, tiny_params,
            process_index=rank, process_count=world,
            gather=gather, sharder=sharder)

    queries = retrieval_data["queries"]
    corpus = retrieval_data["corpus"]
    ref = make("numpy")
    ref.search(queries, corpus, cache=cache)    # warm the cache
    # solo reference: one evaluator.search PER QUERY — what a lone
    # client would get without the frontend
    solo = {}
    for qid, text in queries.items():
        qh, ids, vals = ref.search({qid: text}, corpus, cache=cache)
        assert qh[0] == stable_id_hash(qid)
        solo[qid] = (ids[0], vals[0])
    return {"make": make, "cache": cache, "solo": solo,
            "queries": queries, "corpus": corpus}


def _make_frontend(env, score_impl, world):
    if world == 1:
        ev = env["make"](score_impl)
        return ServeFrontend.from_evaluator(ev, env["corpus"],
                                            env["cache"])
    cluster = SimulatedCluster(world)
    evs = [env["make"](score_impl, rank, world, cluster.gather,
                       cluster.sharder) for rank in range(world)]
    return ServeFrontend.from_cluster(evs, cluster, env["corpus"],
                                      [env["cache"]] * world)


@pytest.mark.parametrize("world", (1, 2))
@pytest.mark.parametrize("score_impl", ("numpy", "jax", "pallas_fused"))
def test_concurrent_submitters_match_solo_search(serve_env, score_impl,
                                                 world):
    """6 submitter threads racing through the frontend get, per query,
    the solo-search result: ids bitwise, scores allclose (the repo's
    cross-impl convention — coalescing changes the GEMM batch shape, so
    low-bit BLAS drift is expected and bounded, rankings are not)."""
    fe = _make_frontend(serve_env, score_impl, world)
    queries = serve_env["queries"]
    out = {}
    lock = threading.Lock()

    def client(item):
        qid, text = item
        ids, vals = fe.submit(text).result(timeout=120)
        with lock:
            out[qid] = (ids[0], vals[0])

    try:
        with ThreadPoolExecutor(6) as pool:
            list(pool.map(client, list(queries.items())))
    finally:
        fe.close()
    assert fe.stats["completed"] == len(queries)
    for qid, (ref_ids, ref_vals) in serve_env["solo"].items():
        ids, vals = out[qid]
        np.testing.assert_array_equal(ids, ref_ids, err_msg=qid)
        np.testing.assert_allclose(vals, ref_vals, rtol=1e-5, atol=1e-6,
                                   err_msg=qid)


def test_mixed_size_requests_match_solo_search(serve_env):
    """Single-query and small-batch requests coalesced into the same
    micro-batches all demux to their solo-search rows."""
    fe = _make_frontend(serve_env, "jax", 1)
    qids = list(serve_env["queries"])
    texts = serve_env["queries"]
    try:
        f_batch = fe.submit([texts[q] for q in qids[:3]])
        f_single = [fe.submit(texts[q]) for q in qids[3:8]]
        ids3, vals3 = f_batch.result(timeout=120)
        for j, qid in enumerate(qids[:3]):
            ref_ids, ref_vals = serve_env["solo"][qid]
            np.testing.assert_array_equal(ids3[j], ref_ids)
            np.testing.assert_allclose(vals3[j], ref_vals, rtol=1e-5,
                                       atol=1e-6)
        for qid, f in zip(qids[3:8], f_single):
            ids, vals = f.result(timeout=120)
            ref_ids, ref_vals = serve_env["solo"][qid]
            np.testing.assert_array_equal(ids[0], ref_ids)
            np.testing.assert_allclose(vals[0], ref_vals, rtol=1e-5,
                                       atol=1e-6)
    finally:
        fe.close()


@pytest.mark.parametrize("score_impl", ("jax", "pallas_fused"))
def test_device_resident_corpus_serves_like_the_streamed_one(serve_env,
                                                             score_impl):
    """A frontend over the device-resident corpus (the default) scans it
    in place, and answers exactly as one streaming the same cached rows
    (``device_resident=False``): ids and scores bitwise."""
    texts = list(serve_env["queries"].values())[:7]
    out, executor = {}, {}
    for resident in (True, False):
        fe = ServeFrontend.from_evaluator(
            serve_env["make"](score_impl), serve_env["corpus"],
            serve_env["cache"], device_resident=resident)
        try:
            out[resident] = fe.search(texts, timeout=120)
            executor[resident] = fe.backend.driver.stats["executor"]
        finally:
            fe.close()
    assert executor == {True: "resident", False: "superchunk"}
    np.testing.assert_array_equal(out[True][0], out[False][0])
    np.testing.assert_array_equal(out[True][1], out[False][1])
    for j, qid in enumerate(list(serve_env["queries"])[:7]):
        np.testing.assert_array_equal(out[True][0][j],
                                      serve_env["solo"][qid][0])


def test_from_evaluator_defaults_come_from_args(serve_env):
    ev = serve_env["make"]("numpy")
    fe = ServeFrontend.from_evaluator(ev, serve_env["corpus"],
                                      serve_env["cache"])
    try:
        assert fe.topk == ev.args.topk == 5
        assert fe.max_batch == ev.args.serve_max_batch == 8
        assert fe.max_wait_s == pytest.approx(
            ev.args.serve_max_wait_ms / 1e3)
    finally:
        fe.close()


# -- launch.serve measurement regressions -------------------------------------


@pytest.fixture(scope="module")
def serve_main_stats(tmp_path_factory):
    """One shared --smoke run of the serve driver (wrap-around batch:
    5 does not divide the 64 synthetic queries)."""
    from repro.launch import serve
    data_dir = str(tmp_path_factory.mktemp("serve_main"))
    return serve.main([
        "--smoke", "--data-dir", data_dir, "--n-requests", "6",
        "--batch", "5", "--concurrency", "3", "--workers", "1",
        "--max-batch", "8", "--max-wait-ms", "2", "--topk", "7"])


def test_serve_main_steady_state_latencies(serve_main_stats):
    """The old loop timed the corpus-encoding warm-up as 'request 0'
    (~80x the steady state).  With the explicit warm pass, request 0 is
    a steady-state sample: within ~2x of request 1 (3x allowed for
    scheduler jitter at ms scale)."""
    lat = serve_main_stats["latencies_ms"]
    assert len(lat) == 6
    assert lat[0] <= 3.0 * lat[1] + 1.0, lat
    assert serve_main_stats["warm_s"] > 0
    # warm-up work really happened outside the timed loop
    assert max(lat) / 1e3 < serve_main_stats["warm_s"]


def test_serve_main_requests_carry_exactly_batch_queries(serve_main_stats):
    """6 requests × 5 queries over 64 ids wraps around instead of
    truncating (the old `q_ids[lo:lo+batch]` bug); main asserts each
    response has exactly (batch, topk) rows, so completing 6 requests
    proves it."""
    fs = serve_main_stats["frontend"]
    # 6 timed requests + the warm rung ladder (1+2+4+8), real rows only
    assert fs["queries"] == 6 * 5 + 15
    assert fs["completed"] == 6 + 4
    assert serve_main_stats["qps"] > 0


def test_backend_classes_validate_world_size(serve_env):
    cluster = SimulatedCluster(2)
    with pytest.raises(ValueError, match="world"):
        ClusterServeBackend([serve_env["make"]("numpy")], cluster,
                            serve_env["corpus"])


def test_evaluator_backend_closes_driver(serve_env):
    ev = serve_env["make"]("numpy")
    backend = EvaluatorServeBackend(ev, serve_env["corpus"],
                                    serve_env["cache"])
    ids, vals = None, None
    fut = backend.begin([next(iter(serve_env["queries"].values()))], 5)
    ids, vals = fut.result(timeout=60)
    assert ids.shape == (1, 5)
    backend.close()
    backend.close()                      # idempotent
