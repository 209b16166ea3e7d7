#!/usr/bin/env python3
"""Bring-up check: the served retrieval path on a TPU at trove-base width.

    python chip_smoke.py              # one chip: phases (a)-(e) below
    python chip_smoke.py --chips 4    # four chips: 4 workers, one per chip,
                                      # against 1 worker on 1 chip

The encoder is trove-base unreduced (12 layers, d_model 768, bf16, vocab
50304) with random weights made from ``--seed``; corpus and queries come
from ``make_retrieval_dataset`` with the same seed, written to a fresh
temporary directory that is removed at the end.  Everything runs in this
one process, which holds the chip(s); it starts no child process.

One chip, through RetrievalEvaluator -> ShardedSearchDriver ->
ServeFrontend, as ``launch/serve.py`` does:

  (a) bulk-encode the corpus into an EmbeddingCache (bucketed EncodePipeline)
  (b) serve the requests, flat index, score_impl="jax"
  (c) the same with score_impl="pallas_fused"
  (d) the same with heap_impl="pallas"
  (e) index_impl="ivf" (pallas_fused), full probe and a small nprobe

Each phase serves the same requests from 4 concurrent submitters and is
compared with a float32 reference: exact ``q @ C.T`` at precision HIGHEST,
then ``lax.top_k``, over the cache's embeddings.  With ``--chips 4`` the
script runs only the sharded path (``--workers 4``, worker r on chip r) and
the one-worker run it must equal.

Every phase prints one ``phase ... {json}`` line: top-10 overlap with the
reference, largest score deviation, and the seconds and latencies of this
one smoke run (not a benchmark).  A failed check exits non-zero after all
phase lines are printed, and without the last line.  The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
script exits non-zero at once when JAX finds no TPU.  The compile cache
lives in ``$JAX_COMPILATION_CACHE_DIR``, else in ``.jax_cache`` here.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
NOTE = "smoke run, not a benchmark"
MIN_FLAT_OVERLAP = 0.99


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_docs: int = 65536
    n_queries: int = 512
    n_requests: int = 64
    batch: int = 8                 # queries per request
    concurrency: int = 4           # submitter threads
    topk: int = 10
    encode_batch: int = 256        # passage encode batch = scan chunk rows
    superchunk: int = 16           # chunks per scan dispatch
    max_batch: int = 32            # micro-batch flush size
    nclusters: int = 256
    nprobe: int = 8


def log(tag: str, **fields) -> None:
    print(f"{tag} {json.dumps(fields, sort_keys=True, default=str)}",
          flush=True)


# -- shared set-up ------------------------------------------------------------


def build_stack(cfg, data_dir: str, sizes: Sizes, seed: int):
    """Dataset, retriever, collator and random params for ``cfg``."""
    import jax

    from repro.core.collator import RetrievalCollator
    from repro.core.config import DataArguments
    from repro.data.synthetic import make_retrieval_dataset
    from repro.data.tokenizer import HashTokenizer
    from repro.models.encoder import DefaultEncoder
    from repro.models.retriever import BiEncoderRetriever

    queries, corpus, _ = make_retrieval_dataset(
        data_dir, n_queries=sizes.n_queries, n_docs=sizes.n_docs, seed=seed)
    retriever = BiEncoderRetriever(DefaultEncoder(cfg), "infonce")
    collator = RetrievalCollator(DataArguments(vocab_size=cfg.vocab_size),
                                 HashTokenizer(cfg.vocab_size))
    params = retriever.init_params(jax.random.key(seed))
    return queries, corpus, retriever, collator, params


def eval_args(sizes: Sizes, **kw):
    from repro.core.config import EvaluationArguments
    return EvaluationArguments(
        topk=sizes.topk, encode_batch_size=sizes.encode_batch,
        superchunk_size=sizes.superchunk, serve_max_batch=sizes.max_batch,
        **kw)


def make_requests(queries: dict, sizes: Sizes) -> list[list[str]]:
    texts = list(queries.values())
    return [[texts[(i * sizes.batch + j) % len(texts)]
             for j in range(sizes.batch)]
            for i in range(sizes.n_requests)]


def encode_phase(ev, corpus: dict, cache) -> None:
    """(a) bulk-encode the corpus into ``cache``."""
    import numpy as np

    view = ev._corpus_view(corpus)
    t0 = time.monotonic()
    ev.encode_corpus(np.asarray(view.id_hashes), view.texts(), cache)
    secs = time.monotonic() - t0
    log("phase a_encode", passages=len(view), cache_rows=len(cache),
        seconds=secs, passages_per_s=len(view) / secs,
        encoder_compiles=ev.encode_pipeline.stats["compiles"], note=NOTE)


def reference(ev, requests, cache, hashes, topk: int) -> dict:
    """Exact float32 top-k of every request's queries over the cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    corpus = jax.device_put(cache.get(hashes).astype(np.float32), ev.device)
    q = jnp.asarray(np.concatenate(
        [np.asarray(ev._encode_texts(r, True, min_batch_dim=1))
         for r in requests]))
    out = {}
    for name, precision in (("exact", jax.lax.Precision.HIGHEST),
                            ("default", None)):
        vals, pos = jax.lax.top_k(
            jnp.dot(q, corpus.T, precision=precision), topk)
        out[name] = (hashes[np.asarray(pos)], np.asarray(vals))
    # how far a default-precision matmul of the same vectors lands from
    # the exact one on this backend (diagnostic, not a check)
    ov, dev = agreement(*out["default"], *out["exact"])
    log("reference", queries=int(q.shape[0]), docs=len(hashes),
        default_precision_overlap=ov, default_precision_max_dev=dev)
    return out


def agreement(ids, scores, ref_ids, ref_scores) -> tuple[float, float]:
    """(mean top-k id overlap, largest rank-wise score deviation)."""
    import numpy as np

    k = ref_ids.shape[1]
    overlap = np.mean([len(set(a.tolist()) & set(b.tolist())) / k
                       for a, b in zip(ids, ref_ids)])
    return float(overlap), float(np.max(np.abs(scores - ref_scores)))


def serve_phase(name: str, frontend, prep_s: float, requests, sizes: Sizes,
                failures: list, during=None) -> dict:
    """Warm every rung, serve ``requests``, close; check that each one
    resolved in full.  ``during(frontend)`` runs before close and its
    result is returned under ``"during"``."""
    import numpy as np

    from repro.launch.serve import run_requests, warm_rungs

    texts = [t for r in requests for t in r]
    try:
        warm_s = warm_rungs(frontend, texts, sizes.max_batch,
                            min_batch=sizes.batch)
        outs, lats, loop_s = run_requests(frontend, requests,
                                          concurrency=sizes.concurrency)
        extra = during(frontend) if during is not None else None
    finally:
        frontend.close()
    st = frontend.stats
    # a plain (ids, scores) tuple is a full-coverage result; only a
    # degraded-capable backend attaches per-query coverage
    if any((np.asarray(o.coverage) < 1.0).any() for o in outs
           if getattr(o, "coverage", None) is not None):
        failures.append(f"{name}: coverage below 1.0")
    if st["degraded"] or st["expired"] or st["failed"]:
        failures.append(f"{name}: degraded/expired/failed {st}")
    if st["completed"] != st["accepted"]:
        failures.append(f"{name}: {st['completed']} of {st['accepted']} "
                        f"requests completed")
    lat_ms = np.asarray(lats) * 1e3
    return {
        "ids": np.concatenate([np.asarray(o[0]) for o in outs]),
        "scores": np.concatenate([np.asarray(o[1]) for o in outs]),
        "during": extra,
        "timing": {"prepare_s": prep_s, "warm_s": warm_s,
                   "steady_s": loop_s,
                   "p50_ms": float(np.percentile(lat_ms, 50)),
                   "p99_ms": float(np.percentile(lat_ms, 99)),
                   "qps": len(texts) / loop_s,
                   "micro_batches": st["batches"],
                   "largest_micro_batch": st["max_batch_seen"],
                   "note": NOTE},
    }


def _report(name: str, res: dict, ref: dict, **extra) -> float:
    overlap, dev = agreement(res["ids"], res["scores"], *ref["exact"])
    res["overlap"] = overlap
    log(f"phase {name}", top10_overlap=overlap, max_score_dev=dev,
        **res["timing"], **extra)
    return overlap


# -- one chip: phases (a)-(e) -------------------------------------------------


def run_one_chip(cfg, work_dir: str, sizes: Sizes = Sizes(),
                 seed: int = 0) -> dict:
    """Phases (a)-(e); returns each serve phase's result.  Raises
    ``AssertionError`` naming every failed check."""
    import numpy as np

    from repro.core.embedding_cache import EmbeddingCache
    from repro.core.evaluator import RetrievalEvaluator
    from repro.core.serving import ServeFrontend

    queries, corpus, retriever, collator, params = build_stack(
        cfg, os.path.join(work_dir, "data"), sizes, seed)
    cache = EmbeddingCache(os.path.join(work_dir, "emb_cache"),
                           dim=cfg.d_model)
    requests = make_requests(queries, sizes)
    k = sizes.nclusters
    phases = [
        ("b_flat_jax", {"score_impl": "jax"}),
        ("c_flat_pallas_fused", {"score_impl": "pallas_fused"}),
        ("d_flat_pallas_heap", {"heap_impl": "pallas"}),
        ("e_ivf_full_probe", {"score_impl": "pallas_fused",
                              "index_impl": "ivf", "ivf_nclusters": k,
                              "ivf_nprobe": k}),
        ("e_ivf_nprobe", {"score_impl": "pallas_fused", "index_impl": "ivf",
                          "ivf_nclusters": k, "ivf_nprobe": sizes.nprobe}),
    ]
    first = RetrievalEvaluator(eval_args(sizes, **phases[0][1]), retriever,
                               collator, params)
    encode_phase(first, corpus, cache)
    hashes = np.asarray(first._corpus_view(corpus).id_hashes)
    ref = reference(first, requests, cache, hashes, sizes.topk)

    failures: list[str] = []
    results = {}
    for name, kw in phases:
        ev = first if name == phases[0][0] else RetrievalEvaluator(
            eval_args(sizes, **kw), retriever, collator, params)
        t0 = time.monotonic()
        frontend = ServeFrontend.from_evaluator(ev, corpus, cache)
        res = serve_phase(name, frontend, time.monotonic() - t0, requests,
                          sizes, failures)
        overlap = _report(name, res, ref, **kw)
        if name.startswith(("b_", "c_", "d_")) and overlap < MIN_FLAT_OVERLAP:
            failures.append(f"{name}: top-10 overlap {overlap} < "
                            f"{MIN_FLAT_OVERLAP}")
        results[name] = res
    full, flat = results["e_ivf_full_probe"], results["c_flat_pallas_fused"]
    same = bool(np.array_equal(full["ids"], flat["ids"]))
    log("check ivf_full_probe_equals_flat_pallas_fused", equal=same,
        max_score_dev=float(np.max(np.abs(full["scores"] - flat["scores"]))))
    if not same:
        failures.append("full-probe IVF ids differ from flat pallas_fused")
    if failures:
        raise AssertionError(failures)
    return results


# -- four chips: the sharded path and what it is compared with ----------------


def device_bytes_in_use() -> dict:
    import jax
    return {str(d): (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.local_devices()}


def run_sharded(cfg, work_dir: str, workers: int, sizes: Sizes = Sizes(),
                seed: int = 0) -> dict:
    """Serve one corpus with ``workers`` in-process workers, worker r on
    local device r, and with one worker on one device; the merged top-k
    ids must be equal.  Raises ``AssertionError`` naming failed checks."""
    import jax
    import numpy as np

    from repro.core.embedding_cache import EmbeddingCache
    from repro.core.evaluator import RetrievalEvaluator
    from repro.core.serving import ServeFrontend
    from repro.launch.distributed import SimulatedCluster

    local = jax.local_devices()
    if len(local) < workers:
        raise ValueError(f"{workers} workers need {workers} local devices; "
                         f"JAX has {len(local)}")
    queries, corpus, retriever, collator, params = build_stack(
        cfg, os.path.join(work_dir, "data"), sizes, seed)
    cache = EmbeddingCache(os.path.join(work_dir, "emb_cache"),
                           dim=cfg.d_model)
    requests = make_requests(queries, sizes)
    args = eval_args(sizes)
    single = RetrievalEvaluator(args, retriever, collator, params,
                                process_index=0, process_count=1)
    encode_phase(single, corpus, cache)
    hashes = np.asarray(single._corpus_view(corpus).id_hashes)
    ref = reference(single, requests, cache, hashes, sizes.topk)
    failures: list[str] = []

    t0 = time.monotonic()
    frontend = ServeFrontend.from_evaluator(single, corpus, cache)
    one = serve_phase("one_worker", frontend, time.monotonic() - t0,
                      requests, sizes, failures,
                      during=lambda fe: device_bytes_in_use())
    _report("one_worker", one, ref, bytes_in_use=one["during"])

    cluster = SimulatedCluster(workers)
    evs = [RetrievalEvaluator(args, retriever, collator, params,
                              process_index=r, process_count=workers,
                              gather=cluster.gather, sharder=cluster.sharder)
           for r in range(workers)]

    def placement(fe) -> dict:
        where = []
        for r, (ev, prep) in enumerate(zip(evs, fe.backend.prepared)):
            where.append({
                "worker": r, "device": str(ev.device),
                "corpus": sorted(map(str, prep.load_chunk(0, 1).devices())),
                "params": sorted({str(d) for leaf in
                                  jax.tree_util.tree_leaves(ev.params)
                                  for d in leaf.devices()})})
        return {"workers": where, "bytes_in_use": device_bytes_in_use()}

    t0 = time.monotonic()
    frontend = ServeFrontend.from_cluster(evs, cluster, corpus,
                                          [cache] * workers)
    many = serve_phase(f"{workers}_workers", frontend,
                       time.monotonic() - t0, requests, sizes, failures,
                       during=placement)
    _report(f"{workers}_workers", many, ref, **many["during"])
    for r, w in enumerate(many["during"]["workers"]):
        want = str(local[r])
        if not (w["device"] == want and w["corpus"] == [want]
                and w["params"] == [want]):
            failures.append(f"worker {r} not placed on {want}: {w}")
    same = bool(np.array_equal(many["ids"], one["ids"]))
    log(f"check {workers}_workers_equal_one_worker", equal=same,
        max_score_dev=float(np.max(np.abs(many["scores"] - one["scores"]))))
    if not same:
        failures.append(f"{workers}-worker ids differ from one worker")
    if one["overlap"] < MIN_FLAT_OVERLAP:
        failures.append(f"one worker: top-10 overlap {one['overlap']} < "
                        f"{MIN_FLAT_OVERLAP}")
    if failures:
        raise AssertionError(failures)
    return {"one": one, "many": many}


# -- entry point --------------------------------------------------------------


def check_mosaic(sizes: Sizes, d: int) -> None:
    """The served scan must lower both Pallas kernels to Mosaic custom
    calls: interpret mode on the chip would hide the device."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    sd = jax.ShapeDtypeStruct
    q, k, s, c = sizes.max_batch, sizes.topk, sizes.superchunk, \
        sizes.encode_batch
    for score, merge in (("pallas_fused", "jax"), ("jax", "pallas")):
        text = ops._superchunk_scan_jit.lower(
            sd((q, k), jnp.float32), sd((q, k), jnp.int32),
            sd((q, d), jnp.float32), sd((s, c, d), jnp.float32),
            sd((s,), jnp.int32), sd((s,), jnp.int32), k=k, score=score,
            merge=merge, interpret=ops._default_interpret()).as_text()
        if "tpu_custom_call" not in text:
            raise SystemExit(f"chip_smoke: the {score}/{merge} scan did not "
                             f"lower to a Mosaic kernel")
    log("check mosaic", kernels=["fused_score_topk", "topk_update"],
        tpu_custom_call=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 = phases (a)-(e) on one chip; 4 = the sharded "
                         "serve path, one worker per chip, vs one worker")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX has "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import repro
    if os.path.dirname(os.path.abspath(repro.__file__)) != os.path.join(
            ROOT, "src", "repro"):
        print(f"chip_smoke: repro imported from {repro.__file__}, not this "
              f"checkout", file=sys.stderr)
        return 2
    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cfg = get_arch("trove-base").cfg
    sizes = Sizes()
    log("config", arch=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
        vocab=cfg.vocab_size, dtype=jax.numpy.dtype(cfg.dtype).name,
        sizes=dataclasses.asdict(sizes), seed=args.seed,
        compile_cache=cache_dir, device_kind=devices[0].device_kind,
        devices=len(devices))
    check_mosaic(sizes, cfg.d_model)
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as work_dir:
        if args.chips == 4:
            run_sharded(cfg, work_dir, 4, sizes, args.seed)
        else:
            run_one_chip(cfg, work_dir, sizes, args.seed)
    log("memory", peak_bytes_in_use=(devices[0].memory_stats() or {}).get(
        "peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
