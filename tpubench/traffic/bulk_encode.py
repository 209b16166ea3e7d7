"""Closed-loop bulk encode: ``RetrievalEvaluator.encode_corpus`` calls of
``call_size`` new passages each, written to an ``EmbeddingCache``.

Set-up makes a pool of passage texts from the seed (every call has the
same multiset of lengths) and runs one call to warm every encoder
shape.  The window runs calls back to back until it has lasted
``--seconds``; every call gets fresh ids, so each one encodes and
writes all its passages.  After the window a seeded sample of the
passages written in it, the longest among them, is read back from the
cache and compared with the reference forward.
"""

from __future__ import annotations

import time

import numpy as np

from tpubench import compare, textgen
from tpubench.system import System


def run(run) -> dict:
    cfg, mix = run.config, run.mix
    size, pool = mix["call_size"], mix["pool_calls"]
    with run.span("tpubench.corpus_prepare"):
        sysm = System(cfg, run.seed)
        cache = sysm.cache(run.work_dir, "encoded")
        texts = [textgen.make_texts(size, mix["passage_words"],
                                    cfg["text"]["words"], run.seed,
                                    f"passage{c}") for c in range(pool)]
    ev = sysm.ev
    run.wrap(ev.encode_pipeline, "tokenize", "tpubench.tokenize")
    run.wrap(cache, "cache_records", "tpubench.cache_write")
    ids = iter(range(10**12))

    def call(c: int) -> np.ndarray:
        batch = np.fromiter(ids, np.int64, count=size)
        with run.span("tpubench.encode_call"):
            ev.encode_corpus(batch, texts[c % pool], cache)
        return batch

    call(0)                                          # warm every rung
    stats0 = dict(ev.encode_pipeline.stats)
    setup_s = time.monotonic() - run.t_start
    written: list[tuple[np.ndarray, int]] = []
    with run.window() as t0:
        c = 0
        while time.monotonic() - t0 < run.seconds:
            written.append((call(c), c % pool))
            c += 1
        t1 = time.monotonic()
    stats1 = dict(ev.encode_pipeline.stats)
    run.read_memory_peak()
    n = size * len(written)
    e2e = {"setup_s": setup_s, "encode_passages_per_s": n / (t1 - t0)}

    with run.span("tpubench.reference"):
        sample = _sample(written, texts, mix["check_sample"], run.seed)
        got = []
        for pid in sample["ids"]:
            got.append(cache.get([int(pid)])[0] if pid in cache else None)
        numbers = compare.encoded(sysm, sample["texts"], got)
    checks = [(name, numbers[name], limit)
              for name, limit in run.workload["limits"].items()]
    print(f"encode {run.cell}: {len(written)} calls of {size} in "
          f"{t1 - t0:.3f} s, numbers "
          f"{ {k: float(v) for k, v in numbers.items()} }", flush=True)
    cap = cfg["passage_max_len"]
    tokens = [min(len(t.split()), cap)
              for _, c in written for t in texts[c]]
    return {"e2e": e2e, "attempted": n, "failed": 0, "checks": checks,
            "window_s": t1 - t0, "encoded_tokens": tokens,
            "counters": {"pipeline": (stats0, stats1),
                         "compiles_in_window": len(run.compiles_in_window()),
                         "calls": len(written)},
            "system": sysm}


def _sample(written, texts, n: int, seed: int) -> dict:
    """``n`` passages written in the window, drawn from the seed, with
    the longest of the first call among them."""
    rng = textgen.rng_for(seed, "check")
    ids, txt = [], []
    for batch, c in written:
        ids.extend(batch.tolist())
        txt.extend(texts[c])
    longest = int(np.argmax([len(t.split()) for t in texts[written[0][1]]]))
    pick = set(rng.choice(len(ids), size=min(n, len(ids)) - 1,
                          replace=False).tolist()) | {longest}
    pick = sorted(pick)
    return {"ids": [ids[i] for i in pick], "texts": [txt[i] for i in pick]}
