"""The numbers that decide ``correct``: the timed path's answers against
the plain reference over the same inputs.

Served answers (one query each, ``(ids (1, k), scores (1, k))``):

* ``unanswered`` -- requests with no answer, or an answer that is not k
  distinct valid ids in descending score order;
* ``score_dev`` -- the widest gap between a served score and the exact
  float32 score of the served id under the reference's query vector;
* ``rank_gap`` -- the widest gap by which the served list, re-scored
  exactly and sorted, lies below the exact top-k at the same rank;
* ``miss_rate`` -- the share of the exact top-k ids absent from the
  served list, over all answered requests.

Encoded passages: ``unreadable`` (passages whose vector is not in the
cache) and ``vec_dev`` (the widest Euclidean distance between a cached
vector and the reference's vector of that passage).
"""

from __future__ import annotations

import numpy as np

from tpubench import reference


def _valid(ids: np.ndarray, scores: np.ndarray, k: int) -> bool:
    return (ids.shape == (k,) and (ids >= 0).all()
            and len(np.unique(ids)) == k
            and bool(np.all(np.diff(scores) <= 0)))


def served(sysm, texts: list[str], answers: list, corpus, k: int,
           precision: str = "float32") -> dict:
    """Numbers for served answers; ``answers[i]`` is the program's
    ``(ids, scores)`` for ``texts[i]`` or ``None``."""
    ok = []
    for a in answers:
        if a is None:
            ok.append(False)
            continue
        ids, scores = (np.asarray(x)[0] for x in a)
        ok.append(_valid(ids, scores, k))
    ok = np.asarray(ok, bool)
    n_ok = int(ok.sum())
    out = {"unanswered": float(len(answers) - n_ok)}
    if n_ok == 0:
        return dict(out, score_dev=None, rank_gap=None, miss_rate=None)
    idx = np.flatnonzero(ok)
    q = reference.encode_texts(sysm.params, [texts[i] for i in idx],
                               sysm.enc, sysm.cfg["query_max_len"],
                               precision=precision)
    ids = np.stack([np.asarray(answers[i][0])[0] for i in idx])
    got = np.stack([np.asarray(answers[i][1])[0] for i in idx])
    top_v, top_i = reference.exact_topk(q, corpus, k)
    return dict(out, **numbers(q, corpus, ids, got, top_v, top_i))


def numbers(q, corpus, ids, got, top_v, top_i) -> dict:
    """``score_dev``, ``rank_gap`` and ``miss_rate`` of served ``ids``
    with served scores ``got`` against the exact top-k ``(top_v,
    top_i)`` of query vectors ``q``."""
    exact = reference.scores_of(q, corpus, ids)
    rescored = -np.sort(-exact, axis=1)
    k = ids.shape[1]
    miss = [len(set(t.tolist()) - set(s.tolist())) / k
            for t, s in zip(top_i, ids)]
    return {"score_dev": float(np.max(np.abs(got - exact))),
            "rank_gap": float(np.max(top_v - rescored)),
            "miss_rate": float(np.mean(miss))}


def control(sysm, texts: list[str], corpus, k: int) -> dict:
    """The control: the reference one precision step down (float8
    encoder and scan) put in the program's place."""
    q8 = reference.encode_texts(sysm.params, texts, sysm.enc,
                                sysm.cfg["query_max_len"], precision="float8")
    v8, i8 = reference.exact_topk(q8, corpus, k, precision="float8")
    q = reference.encode_texts(sysm.params, texts, sysm.enc,
                               sysm.cfg["query_max_len"])
    top_v, top_i = reference.exact_topk(q, corpus, k)
    return dict(numbers(q, corpus, i8, v8, top_v, top_i), unanswered=0.0)


def encoded(sysm, texts: list[str], cached: list,
            precision: str = "float32") -> dict:
    """Numbers for passage vectors read back from the cache
    (``cached[i]`` is ``None`` where the passage is not readable)."""
    rows = [i for i, c in enumerate(cached) if c is not None]
    out = {"unreadable": float(len(cached) - len(rows))}
    if not rows:
        return dict(out, vec_dev=None)
    ref = reference.encode_texts(sysm.params, [texts[i] for i in rows],
                                 sysm.enc, sysm.cfg["passage_max_len"],
                                 precision=precision)
    got = np.stack([cached[i] for i in rows]).astype(np.float32)
    return dict(out, vec_dev=float(np.max(np.linalg.norm(got - ref, axis=1))))
