"""RetrievalEvaluator: unified evaluation + hard-negative mining (§3.5).

One interface, three scales, zero code changes — all three are thin
single-worker instantiations of
:class:`repro.core.sharded_search.ShardedSearchDriver`:

  * single device — one driver (W=1) streams corpus chunks through
    ``encode`` + FastResultHeapq with double-buffered async prefetch
  * multi-device  — corpus chunks sharded over the mesh's data axes by pjit
  * multi-node    — each process runs its driver over a fair-sharded
    corpus slice; local top-k states reduce through a ``ShardGather``
    transport (an O(Q*k*W) reduction, not O(Q*N))

Scoring is a pluggable backend (``EvaluationArguments.score_impl``, see
``sharded_search.SCORE_BACKENDS``), all returning identical rankings:
``numpy`` (host baseline), ``jax`` (device matmul), ``pallas_fused``
(in-kernel score+top-k; the (Q, C) score matrix never materializes).

Embedding caching: encoded chunks are written to the mmap'd
EmbeddingCache; subsequent calls stream cached vectors (paper Table 3
"w/ Cached Embs" path).

Online (cache-less) encoding runs through the bucketed encode pipeline
(``core.encode_pipeline``): background tokenization, ladder-bounded
encoder compiles, device-resident chunks streamed straight into the
driver's superchunk executor.  ``encode_buckets=0`` restores the legacy
per-batch pad-to-longest loop; rankings are identical either way.

Queries and corpora are ``{id: text}`` dicts or lazy
``repro.data.views`` compositions — views stream per chunk through the
driver, so filtered/combined corpora are searched without materialized
copies.  ``evaluate_suite`` builds on that: N datasets evaluated
per-dataset and against their lazily concatenated union, metric tables
written once per suite.
"""

from __future__ import annotations

from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.config import EvaluationArguments
from repro.core.embedding_cache import EmbeddingCache
from repro.core.encode_pipeline import EncodePipeline, PipelineChunkSource
from repro.core.fair_sharding import FairSharder
from repro.core.metrics import compute_metrics
from repro.core.sharded_search import (  # noqa: F401 — re-exported API
    SCORE_BACKENDS, MergeFnGather, ProcessAllGather, ResidentRows,
    ShardedSearchDriver, get_score_backend)
from repro.data.table import stable_id_hash, stable_id_hash_array
from repro.data.views import ConcatView, DatasetView, as_view


def select_hard_negatives(q_ids: Sequence[str], run_ids: np.ndarray,
                          scores: np.ndarray,
                          qrels: dict[str, dict[str, float]],
                          hash_to_raw: dict[int, str],
                          exclude_positives: bool = True
                          ) -> list[tuple[str, str, float]]:
    """Turn ranked (Q, depth) id hashes into negative qrel triplets.

    Vectorized per query: positives are hashed into one int64 array and
    excluded via ``np.isin`` over the whole ranked row, instead of a
    Python set-membership test per (query, rank) item.
    """
    out: list[tuple[str, str, float]] = []
    for qi, q in enumerate(q_ids):
        row = run_ids[qi]
        keep = row >= 0
        if exclude_positives:
            pos = [d for d, g in qrels.get(q, {}).items() if g > 0]
            if pos:
                keep &= ~np.isin(row, stable_id_hash_array(pos))
        out.extend(
            (q, hash_to_raw[h], s)
            for h, s in zip(row[keep].tolist(),
                            scores[qi][keep].tolist()))
    return out


def format_metrics_table(results: dict[str, dict]) -> str:
    """Markdown table: one row per dataset, one column per metric."""
    if not results:
        return "(no results)\n"
    metrics = list(next(iter(results.values())).keys())
    widths = [max(len("dataset"),
                  *(len(n) for n in results))] + [
        max(len(m), 6) for m in metrics]
    def fmt_row(cells):
        return "| " + " | ".join(
            c.ljust(w) for c, w in zip(cells, widths)) + " |\n"
    out = fmt_row(["dataset"] + metrics)
    out += "|" + "|".join("-" * (w + 2) for w in widths) + "|\n"
    for name, vals in results.items():
        out += fmt_row([name] + [f"{vals[m]:.4f}" for m in metrics])
    return out


class PreparedCorpus:
    """A corpus resolved once for repeated searches (serving regime).

    Bundles what :meth:`RetrievalEvaluator.search` used to recompute per
    call: the corpus id hashes, the sized object the FairSharder
    partitions positionally, and the chunk loader (mmap plan / encode
    pipeline / device-resident slices) the driver streams.

    A cache-backed preparation pins a :class:`CacheSnapshot`:
    ``generation`` carries its ``(generation, epoch)`` key, searches
    against this corpus are pinned to exactly that view (concurrent
    mutations and compactions never show through), and with W > 1
    workers the driver hands the key to the sharder so every worker of a
    round provably scores the same snapshot.  :meth:`close` releases the
    pin (so compaction may retire the old epoch's files); non-cache
    corpora have ``generation is None`` and :meth:`close` is a no-op.
    """

    __slots__ = ("hashes", "n_docs", "load_chunk", "sized", "generation",
                 "snapshot")

    def __init__(self, hashes: np.ndarray, n_docs: int, load_chunk,
                 sized=None, generation=None, snapshot=None):
        self.hashes = hashes
        self.n_docs = n_docs
        self.load_chunk = load_chunk
        self.sized = n_docs if sized is None else sized
        self.generation = generation
        self.snapshot = snapshot

    def __len__(self) -> int:
        return self.n_docs

    def close(self) -> None:
        if self.snapshot is not None:
            self.snapshot.close()

    def positions_to_ids(self, pos: np.ndarray) -> np.ndarray:
        """Map the driver's int32 global positions to 63-bit id hashes
        on the host (-1 marks empty slots)."""
        return np.where(pos >= 0, self.hashes[np.clip(pos, 0, None)], -1)

    def round_for(self, q_emb):
        """The ``(sized, load_chunk, positions_to_ids)`` triple for one
        search round against this query batch.

        Flat corpora are query-independent — every round scans the same
        ``[0, n_docs)`` space — so the prepared members come back as-is.
        Index-pruned corpora (:class:`IVFPreparedCorpus`) override this
        to derive a per-batch search space from the query embeddings.
        """
        return self.sized, self.load_chunk, self.positions_to_ids


class IVFSearchSpace:
    """The sized object for one IVF round: the concatenation of the
    selected clusters' permutation slices, positions ``[0, n_selected)``.
    ``partition_boundaries`` exposes the cluster edges inside that space
    so the :class:`~repro.core.fair_sharding.FairSharder` snaps shard
    cuts to whole clusters (each worker then streams a few contiguous
    permutation slices)."""

    __slots__ = ("n_selected", "partition_boundaries")

    def __init__(self, n_selected: int, partition_boundaries: np.ndarray):
        self.n_selected = n_selected
        self.partition_boundaries = partition_boundaries

    def __len__(self) -> int:
        return self.n_selected


class IVFPreparedCorpus(PreparedCorpus):
    """A corpus prepared behind an :class:`repro.index.ivf.IVFIndex`.

    ``fetch_rows(rows)`` serves arbitrary store rows (cache plan /
    materialized array); each :meth:`round_for` call selects this query
    batch's top-``nprobe`` clusters and virtualizes their concatenated
    permutation slices as the round's search space — the driver and
    kernels see an ordinary ``[0, n_selected)`` corpus and run
    completely unchanged.  With ``nprobe == n_clusters`` the space is
    the whole corpus (cluster-permuted), reproducing the flat ranking.
    """

    __slots__ = ("index", "fetch_rows", "nprobe")

    def __init__(self, hashes: np.ndarray, n_docs: int, fetch_rows,
                 index, nprobe: int, generation=None, snapshot=None):
        super().__init__(hashes, n_docs, load_chunk=None,
                         generation=generation, snapshot=snapshot)
        self.index = index
        self.fetch_rows = fetch_rows
        self.nprobe = int(nprobe)

    def round_for(self, q_emb):
        with tracing.span("trove.ivf.select"):
            q = np.asarray(q_emb, np.float32)
            clusters = self.index.select(q, self.nprobe)
            sel_rows = self.index.gather_rows(clusters)
            sized = IVFSearchSpace(len(sel_rows),
                                   self.index.slice_boundaries(clusters))
        fetch = self.fetch_rows

        def load_chunk(lo: int, hi: int):
            with tracing.span("trove.ivf.gather"):
                return fetch(sel_rows[lo:hi])

        def positions_to_ids(pos: np.ndarray) -> np.ndarray:
            if len(sel_rows) == 0:
                return np.full(np.shape(pos), -1, np.int64)
            # sel-space position -> store row -> id hash
            rows = sel_rows[np.clip(pos, 0, None)]
            return np.where(pos >= 0, self.hashes[rows], -1)

        return sized, load_chunk, positions_to_ids


class RetrievalEvaluator:
    def __init__(self, args: EvaluationArguments, retriever, collator,
                 params, mesh=None,
                 process_index: int | None = None,
                 process_count: int | None = None,
                 shard_merge_fn: Callable | None = None,
                 gather=None, sharder: FairSharder | None = None,
                 fault_injector=None):
        self.args = args
        # optional core.faults.FaultInjector threaded into every driver
        # this evaluator builds (chaos tests, serve --chaos)
        self.fault_injector = fault_injector
        self.retriever = retriever
        self.collator = collator
        self.mesh = mesh
        self.process_index = (jax.process_index() if process_index is None
                              else process_index)
        self.process_count = (jax.process_count() if process_count is None
                              else process_count)
        # worker r of an in-process cluster (SimulatedCluster) owns local
        # chip r when the host has a chip per worker: its encoder
        # weights, prepared corpus, query embeddings and heap state all
        # live there.  Otherwise (one worker, a real jax.distributed
        # rank, or more workers than chips) everything stays on the
        # default device.
        local = jax.local_devices()
        self.device = (local[self.process_index]
                       if jax.process_count() == 1
                       and 1 < self.process_count <= len(local) else None)
        if self.device is not None:
            params = jax.device_put(params, self.device)
        self.params = params
        # pass a shared FairSharder (e.g. SimulatedCluster.sharder) so all
        # workers of one cluster see the same throughput-EMA state
        self.sharder = (FairSharder(self.process_count) if sharder is None
                        else sharder)
        # shard-state transport, precedence: explicit merge fn (legacy
        # test injection) > explicit gather > jax.distributed allgather
        if shard_merge_fn is not None:
            self.gather = MergeFnGather(shard_merge_fn)
        elif gather is not None:
            self.gather = gather
        elif self.process_count > 1:
            self.gather = ProcessAllGather()
        else:
            self.gather = None
        self._encode_jit = jax.jit(
            lambda p, b: self.retriever.encoder.encode(p, b))
        # bucketed encode pipeline (encode_buckets=0 -> legacy per-batch
        # pad-to-longest loop, one XLA compile per distinct shape)
        data_args = getattr(collator, "args", None)
        self.encode_pipeline = (EncodePipeline(
            lambda p, b: self.retriever.encoder.encode(p, b),
            collator.tokenizer,
            append_eos=getattr(collator, "append_eos", False),
            pad_to_multiple=getattr(data_args, "pad_to_multiple", 8),
            buckets=args.encode_buckets,
            batch_size=args.encode_batch_size,
            tokenizer_workers=args.tokenizer_workers,
            depth=args.encode_pipeline_depth)
            if args.encode_buckets > 0 and data_args is not None
            and hasattr(collator, "tokenizer") else None)
        # (corpus_obj, key list, DictView): dict corpora are wrapped and
        # hashed once, reused across search/evaluate/mine_hard_negatives.
        self._corpus_view_cache: tuple[dict, list, DatasetView] | None = None

    # -- encoding ------------------------------------------------------------
    def _max_len(self, is_query: bool) -> int | None:
        resolve = getattr(self.collator, "max_len_for", None)
        if resolve is not None:
            return resolve(is_query)
        data_args = getattr(self.collator, "args", None)  # duck-types
        if data_args is None:
            return None
        return (data_args.query_max_len if is_query
                else data_args.passage_max_len)

    def _encode_texts(self, texts: Sequence[str], is_query: bool,
                      max_len: int | None = None,
                      device: bool = False,
                      min_batch_dim: int = 8):
        """Encode texts; ``device=True`` keeps the result device-resident
        (no per-chunk host round-trip) for the device score backends.
        ``min_batch_dim`` floors the pipeline's small-input batch dim
        (the serve frontend passes 1 for latency-proportional
        micro-batches; ignored on the legacy loop)."""
        fmt = (self.retriever.format_query if is_query
               else self.retriever.format_passage)
        bs = (self.args.query_batch_size if is_query
              else self.args.encode_batch_size)
        if max_len is None:
            # queries must truncate/pad at query_max_len, not silently
            # inherit the passage budget
            max_len = self._max_len(is_query)
        if self.encode_pipeline is not None:
            return self.encode_pipeline.encode(
                self.params, list(texts), max_len, fmt=fmt, device=device,
                batch_size=bs, min_batch_dim=min_batch_dim)
        out = []
        for lo in range(0, len(texts), bs):
            chunk = [fmt(t) for t in texts[lo: lo + bs]]
            batch = self.collator.encode_texts(chunk, max_len)
            enc = self._encode_jit(self.params, batch)
            out.append(enc if device else np.asarray(enc))
        if not out:
            return (jnp.empty((0, 0), jnp.float32) if device
                    else np.empty((0, 0), np.float32))
        return jnp.concatenate(out) if device else np.concatenate(out)

    def encode_corpus(self, ids: Sequence, texts: Sequence[str],
                      cache: EmbeddingCache | None = None,
                      device: bool = False):
        """Encode (with cache read/write) the given corpus slice.

        ``device=True`` without a cache keeps encoder output
        device-resident (the online regime: no d2h+h2d round-trip per
        chunk for the device score backends); cache read/write is a host
        path regardless, since the mmap'd cache stores numpy rows."""
        if cache is None and device:
            return self._encode_texts(texts, False, device=True)
        if cache is not None and len(cache):
            have = cache.has(ids)
        else:
            have = np.zeros(len(ids), bool)
        embs = np.empty((len(ids), 0), np.float32)
        missing = np.nonzero(~have)[0]
        if len(missing):
            enc = self._encode_texts([texts[i] for i in missing], False)
            embs = np.empty((len(ids), enc.shape[1]), np.float32)
            embs[missing] = enc
            if cache is not None:
                with tracing.span("trove.cache.write", n=len(missing)):
                    cache.cache_records([ids[i] for i in missing], enc)
        if have.any():
            got = cache.get([ids[i] for i in np.nonzero(have)[0]])
            if embs.shape[1] == 0:
                embs = np.empty((len(ids), got.shape[1]), np.float32)
            embs[np.nonzero(have)[0]] = got
        return embs

    def _corpus_view(self, corpus) -> DatasetView:
        """Coerce a corpus/query container to a lazy view.

        Views pass through (they cache their own id hashes); dicts are
        wrapped in a ``DictView`` memoized per (object, key list) — the
        key-list equality check (cheap C-level compare, pointer fast
        path) rather than identity alone means an in-place mutated dict
        is never served stale hashes.
        """
        if isinstance(corpus, DatasetView):
            return corpus
        if isinstance(corpus, dict):
            keys = list(corpus.keys())
            cached = self._corpus_view_cache
            if (cached is not None and cached[0] is corpus
                    and cached[1] == keys):
                return cached[2]
            view = as_view(corpus)
            self._corpus_view_cache = (corpus, keys, view)
            return view
        return as_view(corpus)

    def _corpus_hashes(self, corpus) -> np.ndarray:
        return np.asarray(self._corpus_view(corpus).id_hashes)

    # -- search ----------------------------------------------------------------
    def make_driver(self) -> ShardedSearchDriver:
        """This evaluator's :class:`ShardedSearchDriver` instantiation —
        the one thin object every search entry point (and the serve
        frontend, which keeps a persistent driver for round-pipelined
        micro-batches) is built on."""
        return ShardedSearchDriver(
            n_workers=self.process_count, worker_index=self.process_index,
            sharder=self.sharder, score_impl=self.args.score_impl,
            heap_impl=self.args.heap_impl,
            chunk_size=self.args.encode_batch_size,
            prefetch=self.args.async_prefetch, gather=self.gather,
            superchunk_size=self.args.superchunk_size,
            superchunk_max_mb=self.args.superchunk_max_mb,
            fault_injector=self.fault_injector,
            round_deadline_s=self.args.round_deadline_s,
            max_shard_retries=self.args.shard_retries,
            retry_backoff_s=self.args.shard_retry_backoff_s)

    def prepare_corpus(self, corpus, cache: EmbeddingCache | None = None,
                       *, device_resident: bool = False) -> "PreparedCorpus":
        """Resolve a corpus ONCE for repeated searches against it.

        Returns a :class:`PreparedCorpus` bundling the id hashes, the
        document count, and the chunk loader the driver streams — the
        cached-corpus ``row_plan``, the online encode-pipeline chunk
        source, or the encode-with-cache fallback, exactly as
        :meth:`search` used to resolve per call.  The serve frontend
        prepares once at startup so per-request work is only
        encode+score+merge.

        ``device_resident=True`` additionally materializes the corpus
        embeddings as one array living where scoring happens (device for
        the device backends, host for ``numpy``): no per-request mmap
        reads or encode.  On the device it is a :class:`ResidentRows`,
        which the driver's scan reads in place; on the host, chunk loads
        are slices.  Encoding (and cache warm-up) happens here, so
        construction is the expensive pass.
        """
        on_device = self.args.score_impl != "numpy"
        corpus_v = self._corpus_view(corpus)
        corpus_texts = corpus_v.texts()
        all_hashes = np.asarray(corpus_v.id_hashes)
        n_docs = len(corpus_v)

        if self.args.index_impl == "ivf" and n_docs > 0:
            return self._prepare_ivf(corpus_v, cache,
                                     device_resident=device_resident)

        if device_resident:
            embs = np.asarray(
                self.encode_corpus(all_hashes, corpus_texts, cache),
                np.float32)
            if not on_device:
                return PreparedCorpus(all_hashes, n_docs,
                                      lambda lo, hi: embs[lo:hi])
            return PreparedCorpus(all_hashes, n_docs, ResidentRows(
                embs, self.args.encode_batch_size, self.device))

        # cached-corpus plan: when the cache already covers the corpus,
        # pin a snapshot and resolve the position->row mapping ONCE (or
        # skip it entirely if the live rows are the corpus order)
        # instead of running a searchsorted per streamed chunk; chunk
        # loads become plain contiguous mmap reads that the driver
        # stacks and uploads once per superchunk.  The snapshot pins the
        # generation: concurrent mutations/compactions never show
        # through this prepared corpus.
        plan = snap = None
        if (cache is not None and len(cache)
                and self.args.use_cached_embeddings):
            snap = cache.snapshot()
            plan = snap.row_plan(all_hashes)
            if plan is None:
                snap.close()
                snap = None

        if plan is None and cache is None and \
                self.encode_pipeline is not None:
            # online regime: the bucketed pipeline streams ordered,
            # (device-resident for device backends) chunks straight into
            # the driver's executor — tokenize overlaps encode, encoder
            # compiles stay ladder-bounded, no per-chunk host round-trip.
            # ``corpus_texts`` is a lazy per-slice sequence, so view rows
            # materialize one pipeline window at a time.
            load_chunk = PipelineChunkSource(
                self.encode_pipeline, self.params,
                corpus_texts, self._max_len(False),
                fmt=self.retriever.format_passage, device=on_device)
        else:
            def load_chunk(lo: int, hi: int):
                if plan is not None:
                    kind, rows = plan
                    if kind == "range":
                        return snap.get_range(lo, hi).astype(np.float32)
                    return snap.get_rows(rows[lo:hi]).astype(np.float32)
                # cache keys are stable hashes, so the already-hashed id
                # slice addresses it for raw-id dicts and views alike
                return self.encode_corpus(
                    all_hashes[lo:hi], corpus_texts[lo:hi], cache,
                    device=on_device)
        return PreparedCorpus(all_hashes, n_docs, load_chunk,
                              sized=corpus_v,
                              generation=snap.key if snap else None,
                              snapshot=snap)

    def _prepare_ivf(self, corpus_v: DatasetView,
                     cache: EmbeddingCache | None, *,
                     device_resident: bool = False) -> "IVFPreparedCorpus":
        """Prepare a corpus behind a cluster-pruned IVF index.

        The coarse quantizer trains off contiguous ``get_range`` streams
        of a corpus-ordered row store — the cache's mmap plan when it
        covers the corpus (no full-corpus materialization), else the
        embeddings encoded here (warming ``cache`` when given).  A
        cache-backed index persists torn-write-safe under
        ``{cache.path}/ivf_k{K}`` keyed by a digest of the corpus hashes
        and build knobs, so repeated serve startups reload instead of
        retraining; any mismatch (corpus changed, knobs changed, torn
        save) silently rebuilds.
        """
        import os

        a = self.args
        on_device = a.score_impl != "numpy"
        all_hashes = np.asarray(corpus_v.id_hashes)
        n_docs = len(corpus_v)
        k = int(min(a.ivf_nclusters, n_docs))

        plan = snap = None
        if (cache is not None and len(cache)
                and a.use_cached_embeddings and not device_resident):
            snap = cache.snapshot()
            plan = snap.row_plan(all_hashes)
            if plan is None:
                snap.close()
                snap = None
        if plan is not None:
            kind, rows_map = plan
            dim = cache.dim
            if kind == "range":
                def get_range(lo, hi):
                    return snap.get_range(lo, hi).astype(np.float32)

                def fetch_rows(rows):
                    return snap.get_rows(rows).astype(np.float32)
            else:
                def get_range(lo, hi):
                    return snap.get_rows(rows_map[lo:hi]).astype(
                        np.float32)

                def fetch_rows(rows):
                    return snap.get_rows(rows_map[rows]).astype(
                        np.float32)
        else:
            # encode now (warming the cache when given) and keep the
            # embeddings as the row store; device-resident for the
            # device backends so chunk loads are zero-copy slices
            embs = np.asarray(
                self.encode_corpus(all_hashes, corpus_v.texts(), cache),
                np.float32)
            dim = embs.shape[1]

            def get_range(lo, hi):
                return embs[lo:hi]

            arr = (jax.device_put(embs, self.device)
                   if device_resident and on_device else embs)

            def fetch_rows(rows):
                return arr[rows]

        from repro.index.ivf import corpus_digest

        # the cache generation is part of the digest: a mutated corpus
        # invalidates the persisted permutation (rebuild) instead of
        # silently loading a layout over a different row set
        digest = corpus_digest(all_hashes, seed=a.ivf_seed,
                               train_steps=a.ivf_train_steps,
                               train_batch=a.ivf_train_batch,
                               generation=snap.key if snap else None)
        index_dir = (os.path.join(cache.path, f"ivf_k{k}")
                     if cache is not None else None)
        index = None
        if index_dir is not None:
            from repro.index import IVFIndex
            index = IVFIndex.load(index_dir, expect_n=n_docs,
                                  expect_dim=dim, expect_clusters=k,
                                  expect_digest=digest)
        if index is None:
            from repro.index import IVFIndex
            index = IVFIndex.build(get_range, n_docs, k, seed=a.ivf_seed,
                                   train_steps=a.ivf_train_steps,
                                   train_batch=a.ivf_train_batch)
            if index_dir is not None:
                index.save(index_dir, digest=digest)
        return IVFPreparedCorpus(all_hashes, n_docs, fetch_rows, index,
                                 a.ivf_nprobe,
                                 generation=snap.key if snap else None,
                                 snapshot=snap)

    def prepare_cache_corpus(self, cache: EmbeddingCache,
                             generation=None) -> "PreparedCorpus":
        """Prepare the cache's *own* live document set for search — the
        live-serving entry point: the corpus is whatever is live in the
        pinned snapshot (adds/updates/deletes included), not an external
        id list.  ``generation`` accepts a ``(generation, epoch)`` key
        (e.g. the agreed key from a :class:`GenerationMismatch`) to pin
        a specific earlier view.  Chunk loads stream live rows straight
        off the snapshot's mmap — preparation is O(live-set) index work,
        no encoding — so swapping to a new generation between serve
        micro-batches is cheap."""
        snap = cache.snapshot(generation)
        if self.args.index_impl == "ivf" and snap.n_live > 0:
            return self._prepare_ivf_snapshot(cache, snap)

        def load_chunk(lo: int, hi: int):
            return snap.get_range(lo, hi).astype(np.float32)

        return PreparedCorpus(snap.ids, snap.n_live, load_chunk,
                              generation=snap.key, snapshot=snap)

    def _prepare_ivf_snapshot(self, cache: EmbeddingCache,
                              snap) -> "IVFPreparedCorpus":
        """IVF preparation over a pinned snapshot's live rows (the
        live-serving counterpart of :meth:`_prepare_ivf`)."""
        import os

        from repro.index import IVFIndex
        from repro.index.ivf import corpus_digest

        a = self.args
        n_docs = snap.n_live
        k = int(min(a.ivf_nclusters, n_docs))

        def get_range(lo, hi):
            return snap.get_range(lo, hi).astype(np.float32)

        def fetch_rows(rows):
            return snap.get_rows(rows).astype(np.float32)

        digest = corpus_digest(snap.ids, seed=a.ivf_seed,
                               train_steps=a.ivf_train_steps,
                               train_batch=a.ivf_train_batch,
                               generation=snap.key)
        index_dir = os.path.join(cache.path, f"ivf_k{k}")
        index = IVFIndex.load(index_dir, expect_n=n_docs,
                              expect_dim=cache.dim, expect_clusters=k,
                              expect_digest=digest)
        if index is None:
            index = IVFIndex.build(get_range, n_docs, k, seed=a.ivf_seed,
                                   train_steps=a.ivf_train_steps,
                                   train_batch=a.ivf_train_batch)
            index.save(index_dir, digest=digest)
        return IVFPreparedCorpus(snap.ids, n_docs, fetch_rows, index,
                                 a.ivf_nprobe, generation=snap.key,
                                 snapshot=snap)

    @staticmethod
    def _with_coverage(items, search_out):
        """Wrap ``items`` as a SearchOutcome when the driver's result
        carried coverage metadata (resilient gather); plain tuple
        otherwise — existing call sites keep unpacking unchanged."""
        coverage = getattr(search_out, "coverage", None)
        if coverage is None:
            return tuple(items)
        from repro.core.faults import SearchOutcome
        return SearchOutcome(items, coverage=coverage,
                             degraded=search_out.degraded)

    def search_prepared(self, queries, prepared: "PreparedCorpus",
                        topk: int | None = None,
                        deadline_s: float | None = None):
        """:meth:`search` against an already-prepared corpus."""
        topk = topk or self.args.topk
        on_device = self.args.score_impl != "numpy"
        q_view = self._corpus_view(queries)
        q_emb = self._encode_texts(q_view.texts(), True, device=on_device)
        driver = self.make_driver()
        sized, load_chunk, to_ids = prepared.round_for(q_emb)
        out = driver.search(q_emb, sized, load_chunk, topk,
                            deadline_s=deadline_s,
                            generation=prepared.generation)
        vals, pos = out
        return self._with_coverage(
            (np.asarray(q_view.id_hashes), to_ids(pos), vals), out)

    def search_texts(self, texts: Sequence[str],
                     prepared: "PreparedCorpus", topk: int | None = None,
                     min_batch_dim: int = 8,
                     deadline_s: float | None = None):
        """Raw-text query search against a prepared corpus — the serve
        backends' entry point (no query-id hashing; requests demux by
        position).  Returns ``(doc_id_hashes (Q, k), scores (Q, k))``
        (a ``SearchOutcome`` with per-query coverage under a resilient
        gather)."""
        topk = topk or self.args.topk
        on_device = self.args.score_impl != "numpy"
        q_emb = self._encode_texts(list(texts), True, device=on_device,
                                   min_batch_dim=min_batch_dim)
        driver = self.make_driver()
        sized, load_chunk, to_ids = prepared.round_for(q_emb)
        out = driver.search(q_emb, sized, load_chunk, topk,
                            deadline_s=deadline_s,
                            generation=prepared.generation)
        vals, pos = out
        return self._with_coverage((to_ids(pos), vals), out)

    def search(self, queries, corpus, topk: int | None = None,
               cache: EmbeddingCache | None = None):
        """Dense retrieval: -> (qid_hashes, doc_id_hashes (Q,k), scores).

        ``queries`` and ``corpus`` are ``{raw_id: text}`` dicts or any
        lazy :class:`~repro.data.views.DatasetView` composition (filter /
        map / select / concat / interleave) — views stream per chunk
        through the driver, so e.g. a ``ConcatView`` corpus is scored
        without the combined corpus ever existing in memory.

        Device-side top-k tracks int32 global corpus *positions*; they are
        mapped back to id hashes here on the host (JAX is 32-bit by
        default — 63-bit hashes would truncate on device).
        """
        return self.search_prepared(queries,
                                    self.prepare_corpus(corpus, cache),
                                    topk)

    # -- public API ---------------------------------------------------------------
    def evaluate(self, queries, corpus,
                 qrels: dict[str, dict[str, float]],
                 cache: EmbeddingCache | None = None) -> dict:
        """Metrics for one (queries, corpus, qrels) scenario.

        ``queries``/``corpus`` may be dicts or lazy views; ``qrels`` may
        be keyed by raw ids or by stable hashes (``stable_id_hash`` is
        the identity on already-hashed int ids).
        """
        out = self.search(queries, corpus, cache=cache)
        q_hashes, run_ids, _ = out
        qrels_h = {
            stable_id_hash(q): {stable_id_hash(d): float(g)
                                for d, g in docs.items()}
            for q, docs in qrels.items()}
        report = compute_metrics(self.args.metrics, run_ids, q_hashes,
                                 qrels_h)
        coverage = getattr(out, "coverage", None)
        if coverage is not None and getattr(out, "degraded", False):
            # a degraded (partially-recovered) search: record how much
            # of the corpus the rankings actually saw, so eval numbers
            # from a faulted run are never mistaken for full-coverage
            report["coverage"] = float(np.asarray(coverage).mean())
            report["degraded"] = True
        return report

    def evaluate_suite(self, scenarios: dict[str, dict], *,
                       combined: bool = True,
                       cache: EmbeddingCache | None = None,
                       out_dir: str | None = None,
                       suite_name: str = "evalsuite") -> dict:
        """Evaluate N datasets — per-dataset AND as one combined corpus.

        ``scenarios`` maps a dataset name to ``{"queries", "corpus",
        "qrels"}`` (dicts or views).  The combined pass concatenates the
        query and corpus *views* (``ConcatView``) and unions the qrels,
        so queries are scored against the union of all corpora without
        the union ever being built on disk or in RAM.  Dataset id
        spaces must be disjoint (namespace your ids per dataset, e.g.
        via ``view.map(..., rekey=True)``) — collisions raise.

        One shared ``cache`` (keyed by stable doc-id hash) serves every
        per-dataset pass and the combined pass.  Runs single- or
        multi-node with zero code changes: under a gather transport
        every worker computes identical tables and only worker 0 writes
        ``{out_dir}/{suite_name}.json`` / ``.md``.
        """
        results: dict[str, dict] = {}
        for name, sc in scenarios.items():
            results[name] = self.evaluate(sc["queries"], sc["corpus"],
                                          sc["qrels"], cache=cache)
        if combined and len(scenarios) > 1:
            q_views = [self._corpus_view(sc["queries"])
                       for sc in scenarios.values()]
            c_views = [self._corpus_view(sc["corpus"])
                       for sc in scenarios.values()]
            for kind, views in (("query", q_views), ("doc", c_views)):
                all_h = np.concatenate(
                    [np.asarray(v.id_hashes) for v in views])
                if len(np.unique(all_h)) != len(all_h):
                    raise ValueError(
                        f"duplicate {kind} ids across suite datasets — "
                        f"namespace ids per dataset (e.g. "
                        f"view.map(..., rekey=True)) before combining")
            merged_qrels: dict = {}
            for sc in scenarios.values():
                merged_qrels.update(sc["qrels"])
            results["combined"] = self.evaluate(
                ConcatView(*q_views), ConcatView(*c_views), merged_qrels,
                cache=cache)
        if out_dir is not None and self.process_index == 0:
            import json
            import os
            os.makedirs(out_dir, exist_ok=True)
            payload = {"suite": suite_name, "metrics": self.args.metrics,
                       "datasets": [n for n in scenarios],
                       "results": results}
            with open(os.path.join(out_dir, f"{suite_name}.json"),
                      "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            with open(os.path.join(out_dir, f"{suite_name}.md"), "w") as f:
                f.write(format_metrics_table(results))
        return results

    def mine_hard_negatives(self, queries, corpus,
                            qrels: dict[str, dict[str, float]],
                            depth: int | None = None,
                            exclude_positives: bool = True,
                            output_path: str | None = None,
                            cache: EmbeddingCache | None = None):
        """Top-ranked non-positives per query -> negative qrel triplets."""
        depth = depth or self.args.topk
        q_ids = self._corpus_view(queries).raw_ids()
        q_hashes, run_ids, scores = self.search(queries, corpus, topk=depth,
                                                cache=cache)
        corpus_v = self._corpus_view(corpus)
        hashes = np.asarray(corpus_v.id_hashes)
        hash_to_raw = dict(zip(hashes.tolist(), corpus_v.raw_ids()))
        out = select_hard_negatives(q_ids, run_ids, scores, qrels,
                                    hash_to_raw, exclude_positives)
        # every worker computes the identical merged triplets (allgather
        # semantics), so only worker 0 writes: W workers racing one
        # shared-FS path would tear or duplicate the file
        if output_path and self.process_index == 0:
            with open(output_path, "w") as f:
                for q, d, s in out:
                    f.write(f"{q}\t{d}\t{s}\n")
        return out
