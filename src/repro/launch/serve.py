"""Retrieval serving driver: prepare a device-resident corpus once, then
answer concurrent query requests through the continuous-batching
:class:`~repro.core.serving.ServeFrontend` (micro-batch coalescing,
admission control, per-request demux).

  python -m repro.launch.serve --data-dir /tmp/trove_data --topk 10

Multi-node story (zero code changes, paper §3.5): the same script serves
from W workers through ``ShardedSearchDriver``.  ``--workers N`` runs N
real driver instances in this process (``SimulatedCluster``); on a real
cluster, launch the script once per node under ``jax.distributed`` (see
``repro.launch.distributed.init_distributed``) and each process takes a
fair-sharded corpus slice automatically.

Measurement discipline (this used to be wrong): corpus encode and XLA
compiles happen in an explicit, separately-reported warm pass *before*
the request loop, so the printed per-request latencies are steady-state.
Requests wrap around the query set so every request carries exactly
``--batch`` queries, and ``--concurrency C`` submits from C threads so
the frontend actually coalesces.  ``main`` returns the stats dict
(per-request latencies, p50/p99, QPS, frontend counters) for tests and
benchmarks.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time


def warm_rungs(frontend, texts, max_batch: int, min_batch: int = 1) -> float:
    """Serve one micro-batch at every power-of-two rung from
    ``min_batch`` up to ``max_batch`` (and ``max_batch`` itself), so every
    encode and scoring shape a coalesced flush can hit is compiled before
    timing starts.  Returns the seconds it took."""
    t0 = time.monotonic()
    widths, b = [], min_batch
    while b < max_batch:
        widths.append(b)
        b *= 2
    widths.append(max_batch)
    for w in widths:
        frontend.search([texts[j % len(texts)] for j in range(w)])
    return time.monotonic() - t0


def run_requests(frontend, requests, *, concurrency: int = 1,
                 deadline_ms: float | None = None):
    """Submit every request (a list of query texts) from ``concurrency``
    threads, retrying on overload, and wait for all of them.  Returns
    ``(outputs, latencies_s, loop_s)``: each request's ``(ids, scores)``
    result in request order, its submit-to-result seconds, and the wall
    time of the whole loop."""
    from repro.core.serving import ServeOverloadError

    outputs = [None] * len(requests)
    latencies = [0.0] * len(requests)

    def submit_one(i: int) -> None:
        t0 = time.monotonic()
        while True:
            try:
                fut = frontend.submit(requests[i], deadline_ms=deadline_ms)
                break
            except ServeOverloadError:
                time.sleep(0.001)      # accepted-or-retried, never dropped
        out = fut.result()
        ids, _ = out
        assert ids.shape == (len(requests[i]), frontend.topk), ids.shape
        latencies[i] = time.monotonic() - t0
        outputs[i] = out

    t_loop = time.monotonic()
    if concurrency > 1:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(concurrency,
                                thread_name_prefix="serve-client") as pool:
            list(pool.map(submit_one, range(len(requests))))
    else:
        for i in range(len(requests)):
            submit_one(i)
    return outputs, latencies, time.monotonic() - t_loop


def main(argv=None):
    import jax
    import numpy as np

    from repro.core.collator import RetrievalCollator
    from repro.core.config import DataArguments, EvaluationArguments
    from repro.core.embedding_cache import EmbeddingCache
    from repro.core.evaluator import RetrievalEvaluator
    from repro.core.serving import ServeFrontend
    from repro.configs import get_arch
    from repro.data.synthetic import make_retrieval_dataset
    from repro.data.tokenizer import HashTokenizer
    from repro.models.encoder import DefaultEncoder
    from repro.models.retriever import BiEncoderRetriever
    from repro.training.checkpoint import (latest_checkpoint,
                                           restore_checkpoint)

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="trove-base")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--data-dir", default="/tmp/trove_data")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--n-requests", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8,
                    help="queries per request (requests wrap around the "
                         "query set so every request has exactly this many)")
    ap.add_argument("--concurrency", type=int, default=1,
                    help="concurrent submitter threads (frontend "
                         "coalesces their requests into micro-batches)")
    ap.add_argument("--workers", type=int, default=0,
                    help="0 = use jax process count (multi-node under "
                         "jax.distributed); 1 = force single-worker; "
                         "N>1 = simulate N workers in-process via "
                         "ShardedSearchDriver")
    ap.add_argument("--score-impl", default="jax",
                    choices=("numpy", "jax", "pallas_fused"))
    ap.add_argument("--index-impl", default="flat",
                    choices=("flat", "ivf"),
                    help="flat = exhaustive scan (recall oracle); ivf = "
                         "cluster-pruned sublinear search (repro.index)")
    ap.add_argument("--nclusters", type=int, default=64,
                    help="IVF coarse-quantizer cluster count")
    ap.add_argument("--nprobe", type=int, default=8,
                    help="clusters scanned per query batch (nprobe == "
                         "nclusters replays the flat ranking)")
    ap.add_argument("--max-batch", type=int, default=32,
                    help="micro-batch flush size (coalesced queries)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="micro-batch flush deadline after first request")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="admission-control bound on pending requests")
    ap.add_argument("--resilient", action="store_true",
                    help="fault-tolerant cluster (workers > 1): a dead "
                         "or silent worker's shard is reassigned to "
                         "survivors instead of aborting the round")
    ap.add_argument("--chaos", default=None,
                    choices=("crash", "stall", "drop"),
                    help="inject one fault of this kind into worker 1 "
                         "at the first steady-state round (requires "
                         "--resilient and --workers > 1)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request latency bound: queued past it -> "
                         "degraded empty result; dispatched -> bounds "
                         "shard-recovery time")
    ap.add_argument("--round-deadline-s", type=float, default=5.0,
                    help="how long a round waits for a silent worker "
                         "before reassigning its shard (resilient only)")
    ap.add_argument("--mutate", action="store_true",
                    help="live-corpus mode: serve the embedding cache's "
                         "generation-versioned live set while a writer "
                         "thread adds/updates/deletes documents and runs "
                         "one online compaction — each micro-batch pins "
                         "the newest committed generation; in-flight "
                         "requests finish on their pinned snapshot")
    args = ap.parse_args(argv)
    if args.chaos and not (args.resilient and args.workers > 1):
        ap.error("--chaos requires --resilient and --workers > 1")

    arch = get_arch(args.arch)
    if args.smoke:
        arch = arch.reduced().variant(dtype=jax.numpy.float32)
    if not os.path.exists(os.path.join(args.data_dir, "queries.jsonl")):
        make_retrieval_dataset(args.data_dir, n_queries=64, n_docs=512,
                               n_topics=32)
    queries, corpus = {}, {}
    for line in open(os.path.join(args.data_dir, "queries.jsonl")):
        rec = json.loads(line)
        queries[rec["_id"]] = rec["text"]
    for line in open(os.path.join(args.data_dir, "corpus.jsonl")):
        rec = json.loads(line)
        corpus[rec["_id"]] = rec["text"]

    tok = HashTokenizer(arch.cfg.vocab_size)
    retriever = BiEncoderRetriever(DefaultEncoder(arch.cfg), "infonce")
    collator = RetrievalCollator(
        DataArguments(vocab_size=arch.cfg.vocab_size), tok)

    params = retriever.init_params(jax.random.key(0))
    if args.ckpt_dir:
        path = latest_checkpoint(args.ckpt_dir)
        if path:
            state = restore_checkpoint(
                path, {"step": np.zeros((), np.int32), "params": params,
                       "opt": {}, "rng": np.zeros(2, np.uint32)})
            params = state["params"]
            print(f"restored {path}")

    eval_args = EvaluationArguments(topk=args.topk,
                                    score_impl=args.score_impl,
                                    index_impl=args.index_impl,
                                    ivf_nclusters=args.nclusters,
                                    ivf_nprobe=args.nprobe,
                                    serve_max_batch=args.max_batch,
                                    serve_max_wait_ms=args.max_wait_ms,
                                    serve_max_queue=args.max_queue,
                                    round_deadline_s=args.round_deadline_s)
    cache = EmbeddingCache(os.path.join(args.data_dir, "emb_cache"),
                           dim=arch.cfg.d_model)

    # one micro-batch = one sharded round; the warm pass below issues
    # exactly len(warm_widths) micro-batches, so the first steady-state
    # round number is known ahead of time — that's where chaos strikes
    n_warm_rounds = 0
    b = 1
    while b < args.max_batch:
        n_warm_rounds += 1
        b *= 2
    n_warm_rounds += 1
    injector = None
    if args.chaos:
        from repro.core.faults import Fault, FaultInjector
        injector = FaultInjector([Fault(
            kind=args.chaos, worker=1, round=n_warm_rounds,
            phase="gather" if args.chaos == "drop" else "load",
            stall_s=2 * args.round_deadline_s)])

    # -- frontend construction (the expensive pass: corpus encode/cache
    # warm-up + driver setup happen here, once) ------------------------------
    t_prep = time.monotonic()
    if args.workers > 1:
        # W real driver instances in this process, deterministic
        # in-memory all-gather — the same code path as W real nodes
        from repro.launch.distributed import SimulatedCluster
        cluster = SimulatedCluster(args.workers, resilient=args.resilient)
        evs = [RetrievalEvaluator(eval_args, retriever, collator, params,
                                  process_index=rank,
                                  process_count=args.workers,
                                  gather=cluster.gather,
                                  sharder=cluster.sharder,
                                  fault_injector=injector)
               for rank in range(args.workers)]
        frontend = ServeFrontend.from_cluster(
            evs, cluster, corpus, [cache] * args.workers,
            live=args.mutate)
        mut_ev = evs[0]
        label = (f"{args.workers} simulated workers"
                 + (" (resilient)" if args.resilient else ""))
    elif args.workers == 1:
        # forced single-worker baseline, even under jax.distributed
        ev = RetrievalEvaluator(eval_args, retriever, collator, params,
                                process_index=0, process_count=1)
        frontend = ServeFrontend.from_evaluator(ev, corpus, cache,
                                                live=args.mutate)
        mut_ev = ev
        label = "1 worker (forced)"
    else:
        # jax process count: 1 standalone, or W under jax.distributed —
        # the evaluator picks the ProcessAllGather transport itself
        ev = RetrievalEvaluator(eval_args, retriever, collator, params)
        frontend = ServeFrontend.from_evaluator(ev, corpus, cache,
                                                live=args.mutate)
        mut_ev = ev
        label = f"{ev.process_count} process(es)"
    prep_s = time.monotonic() - t_prep

    # requests wrap around the query set: every request carries exactly
    # --batch queries (the old `q_ids[lo: lo + batch]` silently truncated
    # the last slice)
    q_ids = list(queries)
    requests = []
    for i in range(args.n_requests):
        texts = [queries[q_ids[(i * args.batch + j) % len(q_ids)]]
                 for j in range(args.batch)]
        assert len(texts) == args.batch, (len(texts), args.batch)
        requests.append(texts)

    # -- explicit warm pass (NOT part of the timed loop): compile the
    # scoring/merge path and every power-of-two encode batch rung a
    # coalesced micro-batch can hit (a micro-batch of Q queries pads to
    # the next rung <= max_batch), so the request loop below measures
    # steady-state serving latency only -------------------------------------
    warm_s = warm_rungs(frontend, [queries[q] for q in q_ids],
                        args.max_batch)
    print(f"prepared corpus ({len(corpus)} docs, cache {len(cache)} rows) "
          f"in {prep_s:.2f}s; warm pass {warm_s * 1e3:.1f} ms on {label}")

    # -- live-corpus writer (--mutate): adds, updates, deletes, and one
    # online compaction run concurrently with the request loop; serving
    # swaps generations between micro-batches, never mid-request ---------------
    mut_thread = None
    mut_stats = {"adds": 0, "updates": 0, "deletes": 0, "compactions": 0}
    gen_start = cache.generation_key
    stop_mut = threading.Event()
    if args.mutate:
        doc_ids = list(corpus)

        def _mutate_loop() -> None:
            i = 0
            # at least two iterations, so every run exercises an add, an
            # update, a delete, and the online compaction even when the
            # request loop finishes first
            while i < 2 or not stop_mut.is_set():
                new_id = f"live-doc-{i}"
                emb = np.asarray(mut_ev._encode_texts(
                    [f"live document {i} arriving mid serve"], False))
                cache.cache_records([new_id], emb)
                mut_stats["adds"] += 1
                upd = doc_ids[i % len(doc_ids)]
                emb = np.asarray(mut_ev._encode_texts(
                    [corpus[upd] + f" revised {i}"], False))
                cache.cache_records([upd], emb)
                mut_stats["updates"] += 1
                if i % 2 == 1:
                    cache.delete_records([f"live-doc-{i - 1}"])
                    mut_stats["deletes"] += 1
                if i == 1:
                    # online compaction: pinned readers keep serving the
                    # retired epoch's files until their rounds drain
                    cache.compact()
                    mut_stats["compactions"] += 1
                i += 1
                stop_mut.wait(0.002)

        mut_thread = threading.Thread(target=_mutate_loop,
                                      name="serve-mutate", daemon=True)
        mut_thread.start()

    _, latencies, loop_s = run_requests(
        frontend, requests, concurrency=args.concurrency,
        deadline_ms=args.deadline_ms)
    if mut_thread is not None:
        stop_mut.set()
        mut_thread.join()
    frontend.close()

    for i, lat in enumerate(latencies):
        print(f"request {i}: {args.batch} queries -> top-{args.topk} "
              f"in {lat * 1e3:.1f} ms on {label}")
    lat_ms = np.sort(np.asarray(latencies)) * 1e3
    p50 = float(np.percentile(lat_ms, 50))
    p99 = float(np.percentile(lat_ms, 99))
    qps = args.n_requests * args.batch / loop_s if loop_s > 0 else 0.0
    fs = frontend.stats
    print(f"steady state: p50 {p50:.1f} ms  p99 {p99:.1f} ms  "
          f"{qps:.1f} queries/s  ({fs['batches']} micro-batches, "
          f"largest {fs['max_batch_seen']} queries)")
    if args.chaos:
        # no-lost-request evidence: the fault really fired, and every
        # accepted request still resolved (submit_one asserts shape, so
        # reaching here means all futures completed)
        assert injector.fired, "chaos fault never fired"
        fault_str = ", ".join(f"{k}@r{r}" for k, r, *_ in
                              ((f.kind, f.round) for f in injector.faults))
        print(f"chaos: injected [{fault_str}] -> {len(injector.fired)} "
              f"fired, {args.n_requests}/{args.n_requests} requests "
              f"resolved, {fs['degraded']} degraded, "
              f"{fs['expired']} expired")
    if args.mutate:
        gen_end = cache.generation_key
        # the writer really ran: generations advanced and every request
        # above still resolved with full-shape results (submit_one
        # asserts), i.e. zero downtime across mutation + compaction
        assert gen_end != gen_start, (gen_start, gen_end)
        assert mut_stats["adds"] > 0, mut_stats
        print(f"mutation: {mut_stats['adds']} adds, "
              f"{mut_stats['updates']} updates, "
              f"{mut_stats['deletes']} deletes, "
              f"{mut_stats['compactions']} compaction(s); generation "
              f"{gen_start} -> {gen_end}, {cache.n_live} live rows, "
              f"{args.n_requests}/{args.n_requests} requests resolved")
    print("serving done")
    return {"label": label, "warm_s": warm_s, "prep_s": prep_s,
            "latencies_ms": [float(x) * 1e3 for x in latencies],
            "p50_ms": p50, "p99_ms": p99, "qps": qps,
            "frontend": dict(fs), "mutation": dict(mut_stats),
            "generation": list(cache.generation_key)}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
