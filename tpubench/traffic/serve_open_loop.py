"""Open-loop single-query requests through ``ServeFrontend.submit``.

Set-up: weights and corpus vectors from the seed, the vectors written to
an ``EmbeddingCache`` as the configuration stores them, the frontend
prepared over them the way a deployment starts it, every encode and
scan shape of the mix warmed.  Window: one generator thread sends each
request at its scheduled time, whether or not earlier ones have come
back; each is timed from its scheduled time to its result.  After the
window every request is awaited, peak memory is read, the program's
state is freed, and every answer is compared with the exact float32
reference over the same vectors.
"""

from __future__ import annotations

import time

import numpy as np

from tpubench import compare, textgen
from tpubench.system import System

AWAIT_AFTER_CLOSE_S = 60.0


def _rungs(max_batch: int) -> list[int]:
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    return out + [max_batch]


def _warm_texts(n: int, words: int, seed: int, spec: dict) -> list[str]:
    lengths = np.full(n, words)
    return textgen.Words(spec["vocab"], spec["zipf"]).texts(
        lengths, textgen.rng_for(seed, "warm"))


def warm(sysm: System, frontend, prepared, mix: dict, seed: int) -> None:
    """Compile every shape the window can meet: the query encoder at each
    (batch rung, length rung) the mix reaches, one full micro-batch at
    each batch rung, and for a pruned corpus every ragged chunk tail."""
    ev, cfg = sysm.ev, sysm.cfg
    qlen = mix["query_words"]
    ladder = ev.encode_pipeline.ladder(cfg["query_max_len"])
    lengths, lo = [], 0
    for rung in ladder:
        top = min(rung, qlen["max"])
        if top >= max(lo + 1, qlen["min"]):
            lengths.append(top)
        lo = rung
    spec = cfg["text"]["words"]
    for b in _rungs(cfg["evaluation"]["serve_max_batch"]):
        for n in lengths:
            ev._encode_texts(_warm_texts(b, n, seed, spec), True,
                             device=True, min_batch_dim=1)
        frontend.search(_warm_texts(b, lengths[-1], seed, spec))
    fetch_rows = getattr(prepared, "fetch_rows", None)
    if fetch_rows is not None:
        import jax.numpy as jnp
        chunk, d = sysm.args.encode_batch_size, cfg["hidden_size"]
        for m in range(1, chunk + 1):
            rows = fetch_rows(np.arange(m, dtype=np.int64))
            if m < chunk:
                jnp.pad(jnp.asarray(rows, jnp.float32),
                        ((0, chunk - m), (0, 0))).block_until_ready()


class Instrumented:
    """The benchmark's spans around the program's layers, and the
    per-round counters read between calls."""

    def __init__(self, run, frontend, prepared):
        self.rounds: list[dict] = []
        self.batch_walls: list[tuple[float, float, int]] = []
        backend = frontend.backend
        run.wrap(backend.ev, "_encode_texts", "tpubench.query_encode")
        index = getattr(prepared, "index", None)
        if index is not None:
            run.wrap(index, "select", "tpubench.ivf_select")
        inner = backend.begin
        driver = backend.driver

        def begin(texts, topk, **kw):
            t0 = time.monotonic()
            with run.span("tpubench.backend_call"):
                fut = inner(texts, topk, **kw)
            self.rounds.append(dict(driver.stats, queries=len(texts)))

            def done(_f, t0=t0, n=len(texts)):
                self.batch_walls.append((t0, time.monotonic(), n))

            fut.add_done_callback(done)
            return fut

        backend.begin = begin


def open_loop(frontend, texts: list[str], sched: np.ndarray, t0: float):
    """Send ``texts[i]`` at ``t0 + sched[i]``; returns per-request
    (scheduled, sent, done) times and futures (``None`` when refused)."""
    from repro.core.serving import ServeError

    n = len(texts)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    futures: list = [None] * n
    for i in range(n):
        due = t0 + sched[i]
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        sent[i] = time.monotonic()
        try:
            fut = frontend.submit(texts[i])
        except ServeError:
            continue
        futures[i] = fut

        def finished(_f, i=i):
            done[i] = time.monotonic()

        fut.add_done_callback(finished)
    return sent, done, futures


def setup(run):
    """Weights, corpus, cache, frontend and warm-up: everything before the
    window.  Returns the state the window and the check need."""
    from repro.core.serving import ServeFrontend

    cfg = run.config
    with run.span("tpubench.corpus_prepare"):
        sysm = System(cfg, run.seed)
        anchors = sysm.anchors()
        vecs = sysm.corpus_vectors(anchors)
        cache = sysm.cache(run.work_dir, "corpus")
        cache.cache_records(np.arange(vecs.shape[0], dtype=np.int64),
                            np.asarray(vecs))
        del vecs
        corpus = dict.fromkeys(range(cfg["num_passages"]), "")
        frontend = ServeFrontend.from_evaluator(sysm.ev, corpus, cache)
        prepared = frontend.backend.prepared
    warm(sysm, frontend, prepared, run.mix, run.seed)
    inst = Instrumented(run, frontend, prepared)
    return {"sysm": sysm, "anchors": anchors, "cache": cache,
            "corpus": corpus, "frontend": frontend, "inst": inst}


def window(run, state: dict, rate: float, seconds: float, seed: int) -> dict:
    """One open-loop window at ``rate`` queries/s; every request is
    awaited (up to a minute past the close)."""
    frontend, inst = state["frontend"], state["inst"]
    sched = textgen.poisson_schedule(rate, seconds, seed)
    texts = textgen.make_texts(len(sched), run.mix["query_words"],
                               run.config["text"]["words"], seed, "query")
    stats0 = dict(frontend.stats)
    n_rounds, n_walls = len(inst.rounds), len(inst.batch_walls)
    with run.window() as t0:
        sent, done, futures = open_loop(frontend, texts, sched, t0)
        deadline = t0 + seconds + AWAIT_AFTER_CLOSE_S
        for f in futures:
            if f is not None:
                try:
                    f.result(timeout=max(0.0, deadline - time.monotonic()))
                except Exception:            # counted as unanswered below
                    pass
    served = []
    for f in futures:
        try:
            served.append(f.result(timeout=0) if f is not None else None)
        except Exception:
            served.append(None)
    answered = np.array([s is not None for s in served])
    lat_ms = np.where(answered, (done - (t0 + sched)) * 1e3, np.inf)
    completed = int(answered.sum())
    last = np.nanmax(done) if completed else np.nan
    lateness_ms = (sent - (t0 + sched)) * 1e3
    return {"texts": texts, "served": served, "completed": completed,
            "sent": len(sched),
            "serve_p95_ms": float(np.percentile(lat_ms, 95)),
            "serve_p50_ms": float(np.percentile(lat_ms, 50)),
            "serve_qps": completed / (last - t0) if completed else None,
            "late_p50_ms": float(np.nanpercentile(lateness_ms, 50)),
            "late_max_ms": float(np.nanmax(lateness_ms)),
            "frontend": (stats0, dict(frontend.stats)),
            "rounds": inst.rounds[n_rounds:],
            "batch_walls": inst.batch_walls[n_walls:],
            "compiles_in_window": run.compiles_in_window()}


def check(run, state: dict, texts: list[str], served: list) -> dict:
    """Free the program's state, then compare every answer with the
    reference over the same corpus vectors."""
    sysm, anchors = state["sysm"], state["anchors"]
    state["frontend"].close()
    for key in ("frontend", "inst", "cache", "corpus"):
        state.pop(key, None)
    run.free()
    with run.span("tpubench.reference"):
        vecs = sysm.corpus_vectors(anchors)
        numbers = compare.served(sysm, texts, served, vecs,
                                 run.config["evaluation"]["topk"])
        del vecs
    return numbers


def run(run) -> dict:
    cell = run.workload
    state = setup(run)
    setup_s = time.monotonic() - run.t_start
    w = window(run, state, cell["rate_qps"], run.seconds, run.seed)
    run.read_memory_peak()
    numbers = check(run, state, w["texts"], w["served"])
    checks = [(name, numbers[name], limit)
              for name, limit in cell["limits"].items()]
    print(f"serve {run.cell}: sent {w['sent']} at {cell['rate_qps']}/s, "
          f"completed {w['completed']}, p50 {w['serve_p50_ms']:.1f} ms, "
          f"generator late p50 {w['late_p50_ms']:.3f} ms max "
          f"{w['late_max_ms']:.3f} ms, compiles in window "
          f"{w['compiles_in_window']}, numbers "
          f"{ {k: v for k, v in numbers.items()} }", flush=True)
    e2e = {"setup_s": setup_s, "serve_p95_ms": w["serve_p95_ms"],
           "serve_qps": w["serve_qps"]}
    return {"e2e": e2e, "attempted": w["sent"],
            "failed": w["sent"] - w["completed"], "checks": checks,
            "counters": {k: w[k] for k in ("frontend", "rounds",
                                           "batch_walls",
                                           "compiles_in_window")},
            "queries": w["texts"], "system": state["sysm"]}
