"""The program's spans (core.tracing): off by default and free when off;
nesting, threads, the buffer bound, cross-thread records and compile
attribution when on; and the spans a served request and an encode call
leave behind."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tracing
from repro.core.encode_pipeline import EncodePipeline
from repro.core.evaluator import PreparedCorpus
from repro.core.serving import EvaluatorServeBackend, ServeFrontend
from repro.core.sharded_search import ShardedSearchDriver
from repro.data.tokenizer import HashTokenizer


@pytest.fixture()
def traced():
    tracing.enable()
    try:
        yield
    finally:
        tracing.disable()
        tracing.records()


def by_name(recs):
    out = {}
    for r in recs:
        out.setdefault(r.name, []).append(r)
    return out


def test_off_by_default_records_nothing_and_shares_one_context():
    assert not tracing.enabled()
    a, b = tracing.span("trove.a", x=1), tracing.span("trove.b")
    assert a is b
    with a:
        with tracing.span("trove.c"):
            tracing.annotate(round=3)
    tracing.record("trove.q", 0.0, 1.0, request=0)
    assert tracing.records() == []
    assert tracing.dropped() == 0


def test_nesting_gives_parents_and_threads_keep_their_own_stacks(traced):
    ready, go = threading.Event(), threading.Event()

    def other():
        with tracing.span("trove.other"):
            ready.set()
            assert go.wait(5)

    t = threading.Thread(target=other, name="side")
    with tracing.span("trove.outer", batch=7):
        t.start()
        assert ready.wait(5)
        with tracing.span("trove.inner"):
            tracing.annotate(round=2)
        go.set()
        t.join(5)
    assert not t.is_alive()
    recs = by_name(tracing.records())
    (outer,), (inner,), (side,) = (recs["trove.outer"], recs["trove.inner"],
                                   recs["trove.other"])
    assert outer.parent is None and outer.ids == {"batch": 7}
    assert inner.parent == "trove.outer" and inner.ids == {"round": 2}
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    # opened while trove.outer was open on the main thread: no parent
    assert side.parent is None and side.thread == "side"
    assert inner.thread == outer.thread != "side"


def test_buffer_bound_counts_what_it_drops(traced, monkeypatch):
    monkeypatch.setattr(tracing, "BOUND", 5)
    for _ in range(8):
        with tracing.span("trove.x"):
            pass
    assert tracing.dropped() == 3
    assert len(tracing.records()) == 5
    assert tracing.records() == []          # handed over and cleared
    tracing.enable()                         # a fresh recording
    assert tracing.dropped() == 0


def test_record_spans_threads(traced):
    import time
    t0 = time.monotonic()
    stamps = []
    t = threading.Thread(target=lambda: stamps.append(time.monotonic()))
    t.start()
    t.join(5)
    tracing.record("trove.serve.queue", t0, stamps[0], request=4, batch=1)
    (rec,) = tracing.records()
    assert rec.name == "trove.serve.queue" and rec.parent is None
    assert rec.t0 == t0 and rec.t1 == stamps[0]
    assert rec.ids == {"request": 4, "batch": 1}


def test_compiles_go_to_the_innermost_span(traced):
    f = jax.jit(lambda x: x * 3 + 1)
    with tracing.span("trove.outer"):
        with tracing.span("trove.inner"):
            f(jnp.ones(17)).block_until_ready()        # compiles
        with tracing.span("trove.again"):
            f(jnp.ones(17)).block_until_ready()        # cached
    recs = {r.name: r for r in tracing.records()}
    assert recs["trove.inner"].compiles >= 1
    assert recs["trove.again"].compiles == 0
    assert recs["trove.outer"].compiles == 0


# -- the served path ----------------------------------------------------------


N_DOCS, DIM = 230, 16


class _TinyEvaluator:
    """Stands in for a RetrievalEvaluator: query embeddings hashed from
    the text, a host corpus, and the tiny superchunk driver of
    ``test_superchunk``."""

    class args:
        score_impl = "jax"

    def __init__(self):
        rng = np.random.default_rng(11)
        self.docs = rng.normal(size=(N_DOCS, DIM)).astype(np.float32)

    def prepare_corpus(self, corpus, cache=None, device_resident=True):
        return PreparedCorpus(np.arange(N_DOCS, dtype=np.int64), N_DOCS,
                              lambda lo, hi: self.docs[lo:hi])

    def make_driver(self):
        return ShardedSearchDriver(score_impl="jax", heap_impl="jax",
                                   chunk_size=37, superchunk_size=3)

    def _encode_texts(self, texts, is_query, device=False,
                      min_batch_dim=1):
        rows = [np.random.default_rng(int(t[1:])).normal(size=DIM)
                for t in texts]
        return jnp.asarray(np.asarray(rows, np.float32))


def test_served_requests_join_queue_batch_round_and_reduce(traced):
    backend = EvaluatorServeBackend(_TinyEvaluator(), None)
    with ServeFrontend(backend, topk=5, max_batch=4, max_wait_ms=5) as fe:
        futs = [fe.submit(f"q{i}") for i in range(11)]
        for f in futs:
            f.result(timeout=60)
    recs = by_name(tracing.records())
    queue = recs["trove.serve.queue"]
    assert sorted(r.ids["request"] for r in queue) == list(range(11))
    batches = {r.ids["batch"]: r for r in recs["trove.serve.batch"]}
    rounds = {r.ids["round"] for r in recs["trove.search.score"]}
    reduces = {r.ids["round"] for r in recs["trove.search.reduce"]}
    demuxed = {r.ids["batch"] for r in recs["trove.serve.demux"]}
    for q in queue:
        b = batches[q.ids["batch"]]
        assert q.t0 <= q.t1 <= b.t0 + 1e-3
        assert b.ids["round"] in rounds and b.ids["round"] in reduces
        assert q.ids["batch"] in demuxed
    for s in recs["trove.search.score"]:
        assert s.parent == "trove.serve.batch"
    assert sum(b.ids["n_real"] for b in batches.values()) == 11
    assert {"trove.search.load", "trove.search.wait", "trove.search.tile",
            "trove.search.scan", "trove.serve.collect"} <= set(recs)
    assert all(r.thread.startswith("chunk-prefetch")
               for r in recs["trove.search.load"])
    assert tracing.dropped() == 0


def test_encode_tokenizes_before_it_runs(traced):
    def encode_fn(params, batch):
        return (batch["tokens"] * batch["mask"]).sum(-1, keepdims=True
                                                     ).astype(jnp.float32)

    pipe = EncodePipeline(encode_fn, HashTokenizer(512), buckets=3,
                          batch_size=8, tokenizer_workers=2, depth=0)
    texts = [" ".join(f"w{j}" for j in range(1 + i % 9)) for i in range(20)]
    pipe.encode(None, texts, 16)
    recs = tracing.records()
    names = [r.name for r in recs]
    assert names.count("trove.encode.tokenize") == 1
    assert names.count("trove.encode.run") == 3           # 20 rows, 8/batch
    assert names.count("trove.encode.fetch") == 1
    tok = next(r for r in recs if r.name == "trove.encode.tokenize")
    runs = [r for r in recs if r.name == "trove.encode.run"]
    assert tok.ids == {"n": 20}
    assert all(tok.t1 <= r.t0 for r in runs)
    assert {r.ids["rung"] for r in runs} <= set(pipe.ladder(16))
    assert "batches" not in pipe.stats and "windows" not in pipe.stats
