"""Lazy dataset-view algebra vs an eagerly materialized oracle.

Every combinator (filter / map / select / concat / interleave) and
nested compositions thereof must agree with the obvious eager
implementation — rows, ids, and end-to-end search rankings bitwise —
while materializing only touched rows.
"""

import numpy as np
import pytest

from repro.core.collator import RetrievalCollator
from repro.core.config import DataArguments, EvaluationArguments
from repro.core.evaluator import RetrievalEvaluator
from repro.data.table import stable_id_hash
from repro.data.tokenizer import HashTokenizer
from repro.data.views import (ConcatView, DatasetView, FilterView,
                              InterleaveView, MapView, RecordsView,
                              SelectView, TableView, ViewTexts, as_view,
                              row_text)

from tests._hypothesis_shim import given, settings, st


def recs(n, prefix="r", start=0):
    return [{"_id": f"{prefix}{start + i}", "text": f"text {prefix} {i} "
             + "x" * (i % 7)} for i in range(n)]


def eager(view: DatasetView) -> list[dict]:
    """The oracle: materialize everything."""
    return [view.row(i) for i in range(len(view))]


def assert_matches(view, expected_rows):
    """View == eager reference on every access surface."""
    assert len(view) == len(expected_rows)
    assert eager(view) == expected_rows
    assert view.rows(0, len(view)) == expected_rows
    want_ids = [r.get("_id") for r in expected_rows]
    np.testing.assert_array_equal(
        view.id_hashes, [stable_id_hash(i) for i in want_ids])
    assert view.raw_ids() == want_ids
    assert list(view.texts()) == [row_text(r) for r in expected_rows]
    for i in (0, len(expected_rows) - 1):
        if expected_rows:
            assert view.get(want_ids[i]) == expected_rows[i]
            # a repeated id resolves to its first position
            assert view.index_of(want_ids[i]) == want_ids.index(want_ids[i])
            assert want_ids[i] in view
    assert "no-such-id" not in view


# -- single combinators vs oracle ---------------------------------------------


def test_records_leaf_roundtrip():
    r = recs(13)
    assert_matches(RecordsView(r), r)


def test_dict_leaf_matches_mapping():
    d = {f"k{i}": f"v{i}" for i in range(9)}
    v = as_view(d)
    assert_matches(v, [{"_id": k, "text": t} for k, t in d.items()])
    assert v.raw_ids() == list(d)


def test_filter_matches_eager():
    r = recs(31)
    pred = lambda rec: len(rec["text"]) % 3 == 0          # noqa: E731
    assert_matches(RecordsView(r).filter(pred),
                   [x for x in r if pred(x)])


def test_filter_is_lazy_until_first_access():
    calls = []

    def pred(rec):
        calls.append(rec["_id"])
        return True

    v = RecordsView(recs(8)).filter(pred)
    w = ConcatView(v, RecordsView(recs(3, "o")))   # composing stays free
    assert calls == []
    assert len(w) == 11                            # first access scans once
    assert len(calls) == 8
    len(w)
    assert len(calls) == 8                         # index is cached


def test_map_matches_eager():
    r = recs(17)
    fn = lambda rec: {**rec, "text": rec["text"].upper()}  # noqa: E731
    v = RecordsView(r).map(fn)
    assert_matches(v, [fn(x) for x in r])


def test_map_rekey_recomputes_hashes():
    r = recs(6)
    fn = lambda rec: {**rec, "_id": "ns-" + rec["_id"]}    # noqa: E731
    v = RecordsView(r).map(fn, rekey=True)
    assert_matches(v, [fn(x) for x in r])
    assert v.index_of("ns-r3") == 3
    # without rekey, ids are answered from the parent
    np.testing.assert_array_equal(
        RecordsView(r).map(fn).id_hashes, RecordsView(r).id_hashes)


def test_select_positions_ids_mask_negative():
    r = recs(10)
    base = RecordsView(r)
    assert_matches(base.select([7, 2, 2, 0]),
                   [r[7], r[2], r[2], r[0]])
    assert_matches(base.select(["r4", "r9"]), [r[4], r[9]])
    mask = np.zeros(10, bool)
    mask[[1, 5]] = True
    assert_matches(base.select(mask), [r[1], r[5]])
    assert_matches(base.select([-1, -10]), [r[9], r[0]])
    with pytest.raises(IndexError):
        base.select([10])
    with pytest.raises(IndexError):
        base.select(np.zeros(4, bool))
    with pytest.raises(KeyError):
        base.select(["nope"])


def test_concat_matches_eager():
    a, b, c = recs(5, "a"), recs(0, "b"), recs(7, "c")
    v = ConcatView(RecordsView(a), RecordsView(b), RecordsView(c))
    assert_matches(v, a + b + c)
    assert_matches(RecordsView(a) + RecordsView(c), a + c)
    assert_matches(RecordsView(a).concat(RecordsView(b), RecordsView(c)),
                   a + b + c)
    assert v.row(-1) == c[-1]
    # spans crossing child boundaries
    assert v.rows(3, 9) == (a + c)[3:9]


def test_interleave_round_robin_order():
    a, b = recs(4, "a"), recs(2, "b")
    v = InterleaveView(RecordsView(a), RecordsView(b))
    want = [a[0], b[0], a[1], b[1], a[2], a[3]]   # b drops out after 2
    assert_matches(v, want)


def test_nested_composition_matches_eager():
    r = recs(40)
    pred = lambda rec: int(rec["_id"][1:]) % 2 == 0        # noqa: E731
    fn = lambda rec: {**rec, "text": rec["text"][::-1]}    # noqa: E731
    other = recs(11, "z")
    v = (RecordsView(r).filter(pred).map(fn)
         + RecordsView(other)).select(list(range(0, 25, 2))[::-1])
    ref = [fn(x) for x in r if pred(x)] + other
    ref = [ref[i] for i in list(range(0, 25, 2))[::-1]]
    assert_matches(v, ref)
    deep = v.interleave(RecordsView(recs(3, "w"))).filter(
        lambda rec: not rec["_id"].startswith("w"))
    assert_matches(deep, ref)


# -- streaming contract -------------------------------------------------------


@pytest.mark.parametrize("lo,hi,chunk", [(0, 23, 5), (3, 17, 4),
                                         (0, 23, 64), (7, 7, 3)])
def test_open_slice_ordered_chunks(lo, hi, chunk):
    r = recs(23)
    v = RecordsView(r)
    got, offs = [], []
    for off, rows in v.open_slice(lo, hi, chunk):
        offs.append(off)
        assert len(rows) <= chunk
        got.extend(rows)
    assert got == r[lo:hi]
    assert offs == list(range(lo, hi, chunk))


def test_open_slice_clamps_hi_and_evicts():
    evicted = []

    class Spy(RecordsView):
        def evict(self, lo, hi):
            evicted.append((lo, hi))

    v = Spy(recs(10))
    rows = [r for _, chunk in v.open_slice(0, 999, 4) for r in chunk]
    assert len(rows) == 10
    assert evicted == [(0, 4), (4, 8), (8, 10)]


def test_combinators_propagate_evict():
    evicted = []

    class Spy(RecordsView):
        def evict(self, lo, hi):
            evicted.append((lo, hi))

    v = (Spy(recs(12)).filter(lambda r: True)
         + Spy(recs(4, "b"))).select(list(range(14)))
    list(v.open_slice(0, len(v), 6))
    assert evicted                                 # reached the leaves
    assert all(0 <= lo < hi <= 12 for lo, hi in evicted)


def test_viewtexts_lazy_sequence():
    r = recs(9)
    t = ViewTexts(RecordsView(r))
    want = [row_text(x) for x in r]
    assert len(t) == 9
    assert t[4] == want[4]
    assert t[2:7] == want[2:7]
    assert t[1:8:3] == want[1:8:3]
    assert list(t) == want
    assert t[-2:] == want[-2:]


def test_table_view_over_mmap(retrieval_data, tmp_path):
    from repro.core.config import MaterializedQRelConfig
    from repro.core.materialized_qrel import MaterializedQRel
    d = retrieval_data["dir"]
    m = MaterializedQRel(MaterializedQRelConfig(
        qrel_path=f"{d}/qrels/train.tsv", query_path=f"{d}/queries.jsonl",
        corpus_path=f"{d}/corpus.jsonl"), str(tmp_path))
    v = m.corpus_view()
    assert isinstance(v, TableView)
    assert len(v) == len(retrieval_data["corpus"])
    for did, text in list(retrieval_data["corpus"].items())[:5]:
        assert v.get(did)["text"] == text
        assert v.text(v.index_of(did)) == m.doc_text(stable_id_hash(did))
    # a full streaming scan (with page eviction) sees every row once
    seen = [r["_id"] for _, rows in v.open_slice(0, len(v), 7)
            for r in rows]
    assert seen == list(retrieval_data["corpus"])


def test_as_view_coercions():
    v = RecordsView(recs(3))
    assert as_view(v) is v
    assert isinstance(as_view({"a": "t"}), DatasetView)
    assert isinstance(as_view(recs(2)), RecordsView)
    assert len(as_view([])) == 0
    with pytest.raises(TypeError):
        as_view(42)


# -- end-to-end: rankings through views == rankings through dicts -------------


def _evaluator(tiny_retriever, tiny_params, score_impl, **kw):
    coll = RetrievalCollator(DataArguments(vocab_size=257),
                             HashTokenizer(257))
    return RetrievalEvaluator(
        EvaluationArguments(topk=10, score_impl=score_impl,
                            metrics=("ndcg@10", "recall@10")),
        tiny_retriever, coll, tiny_params, **kw)


@pytest.mark.parametrize("score_impl", ("numpy", "jax", "pallas_fused"))
def test_search_views_bitwise_equals_dicts(tiny_retriever, tiny_params,
                                           retrieval_data, score_impl):
    """Composed lazy corpus == eager dict corpus, identical rankings."""
    ev = _evaluator(tiny_retriever, tiny_params, score_impl)
    corpus = retrieval_data["corpus"]
    qh_ref, ids_ref, s_ref = ev.search(retrieval_data["queries"], corpus)

    items = list(corpus.items())
    half = len(items) // 2
    view = ConcatView(
        RecordsView([{"_id": k, "text": t} for k, t in items[:half]]),
        as_view(dict(items[half:])))
    q_view = as_view(retrieval_data["queries"])
    qh, ids, s = ev.search(q_view, view)
    np.testing.assert_array_equal(qh, qh_ref)
    np.testing.assert_array_equal(ids, ids_ref)
    np.testing.assert_array_equal(s, s_ref)


def test_search_filtered_view_equals_filtered_dict(tiny_retriever,
                                                   tiny_params,
                                                   retrieval_data):
    ev = _evaluator(tiny_retriever, tiny_params, "jax")
    corpus = retrieval_data["corpus"]
    keep = {k: t for k, t in corpus.items() if "topic1" not in t}
    assert 0 < len(keep) < len(corpus)
    _, ids_ref, s_ref = ev.search(retrieval_data["queries"], keep)
    view = as_view(corpus).filter(lambda r: "topic1" not in r["text"])
    _, ids, s = ev.search(retrieval_data["queries"], view)
    np.testing.assert_array_equal(ids, ids_ref)
    np.testing.assert_array_equal(s, s_ref)


@pytest.mark.distributed
@pytest.mark.parametrize("w", (2,))
def test_search_views_sharded_equals_single(tiny_retriever, tiny_params,
                                            retrieval_data, w):
    """W simulated workers over a ConcatView == single process."""
    from repro.launch.distributed import SimulatedCluster
    ev = _evaluator(tiny_retriever, tiny_params, "jax")
    corpus = retrieval_data["corpus"]
    items = list(corpus.items())
    half = len(items) // 2

    def make_view():
        return ConcatView(as_view(dict(items[:half])),
                          as_view(dict(items[half:])))

    _, ids_ref, s_ref = ev.search(retrieval_data["queries"], make_view())
    cluster = SimulatedCluster(w)
    evs = [_evaluator(tiny_retriever, tiny_params, "jax",
                      process_index=rank, process_count=w,
                      gather=cluster.gather, sharder=cluster.sharder)
           for rank in range(w)]
    outs = cluster.run(lambda rank: evs[rank].search(
        retrieval_data["queries"], make_view()))
    for _, ids, s in outs:
        np.testing.assert_array_equal(ids, ids_ref)
        np.testing.assert_array_equal(s, s_ref)


# -- property tests (skip individually when hypothesis is absent) -------------


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 60), st.integers(1, 17), st.integers(0, 7))
def test_property_open_slice_partitions(n, chunk, mod):
    r = recs(n)
    v = RecordsView(r).filter(lambda rec: len(rec["text"]) % 7 != mod)
    want = [x for x in r if len(x["text"]) % 7 != mod]
    got = [x for _, rows in v.open_slice(0, len(v), chunk) for x in rows]
    assert got == want


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(0, 25), max_size=30), st.integers(1, 4))
def test_property_compositions_match_eager(positions, k):
    parts = [recs(9, f"p{j}") for j in range(k)]
    flat = [x for p in parts for x in p]
    v = ConcatView(*[RecordsView(p) for p in parts])
    sel = [p % len(flat) for p in positions]
    assert_matches(v.select(sel), [flat[i] for i in sel])
    inter = InterleaveView(*[RecordsView(p) for p in parts])
    ref = [p[i] for i in range(9) for p in parts]
    assert_matches(inter, ref)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 40), st.integers(0, 40), st.integers(1, 9))
def test_property_concat_rows_spans(a_n, b_n, chunk):
    a, b = recs(a_n, "a"), recs(b_n, "b")
    v = RecordsView(a) + RecordsView(b)
    ref = a + b
    for lo in range(0, len(ref) + 1, chunk):
        hi = min(lo + chunk * 2, len(ref))
        assert v.rows(lo, hi) == ref[lo:hi]
