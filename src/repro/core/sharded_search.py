"""ShardedSearchDriver: the multi-node search engine (paper §3.5).

The paper's claim — "the same script runs on any number of nodes, and
inference time decreases linearly with the number of available nodes" —
is implemented here as a coordinator/worker driver that every search
entry point (``RetrievalEvaluator.search``, ``mine_hard_negatives``,
``launch.serve``, ``benchmarks.bench_multinode``) instantiates:

  * **partition** — the coordinator splits ``[0, n_docs)`` across workers
    with :class:`~repro.core.fair_sharding.FairSharder` (throughput EMA,
    updated after every round, so stragglers shrink next round);
  * **stream**    — each worker pulls its slice in ``chunk_size`` chunks
    through a caller-supplied ``load_chunk(lo, hi)`` (cache read / encode
    / h2d) with **double-buffered async prefetch**: chunk ``i+1``'s load
    overlaps chunk ``i``'s scoring on the worker's main thread; a corpus
    already resident on the device (:class:`ResidentRows`) is instead
    read in place inside the jitted scan, with no per-chunk load;
  * **score**     — a pluggable backend (``SCORE_BACKENDS``) folds each
    chunk into a local :class:`FastResultHeapq` (Q, k) state;
  * **reduce**    — per-worker states merge through a
    :class:`ShardGather` transport via ``FastResultHeapq.merge_arrays``:
    an ``O(Q·k·W)`` reduction, never ``O(Q·N)``.

Transports: :class:`ProcessAllGather` (real multi-node via
``jax.distributed``) and ``repro.launch.distributed.InMemoryAllGather``
(W real drivers in one process — tests/benchmarks) are interchangeable;
all of them merge rank states in rank order, so every worker computes an
identical merged ranking.
"""

from __future__ import annotations

import math
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.fair_sharding import FairSharder
from repro.core.faults import (FaultInjector, InjectedTransportDrop,
                               SearchOutcome)
from repro.core.result_heap import FastResultHeapq

# -- score backends -----------------------------------------------------------
#
# A backend folds one corpus-embedding chunk into the running heap:
#   backend(q_emb, chunk_embs, id_offset, heap, k)
# where id_offset is the chunk's global corpus position (int32 positions
# on device; the host maps positions back to 63-bit id hashes).

_matmul_jit = jax.jit(lambda q, d: q @ d.T)


def _score_numpy(q_emb, embs, id_offset: int, heap: FastResultHeapq,
                 k: int) -> None:
    positions = np.arange(id_offset, id_offset + embs.shape[0],
                          dtype=np.int32)
    heap.update(np.asarray(q_emb) @ np.asarray(embs).T, positions)


def _score_jax(q_emb, embs, id_offset: int, heap: FastResultHeapq,
               k: int) -> None:
    scores = _matmul_jit(jnp.asarray(q_emb), jnp.asarray(embs))
    positions = jnp.arange(id_offset, id_offset + embs.shape[0],
                           dtype=jnp.int32)
    heap.update(scores, positions)


def _score_pallas_fused(q_emb, embs, id_offset: int, heap: FastResultHeapq,
                        k: int) -> None:
    from repro.kernels import ops as kops
    vals, ids = kops.fused_score_topk(jnp.asarray(q_emb), jnp.asarray(embs),
                                      k, id_offset=id_offset)
    heap.merge_arrays(vals, ids)


SCORE_BACKENDS: dict[str, Callable] = {
    "numpy": _score_numpy,
    "jax": _score_jax,
    "pallas_fused": _score_pallas_fused,
}


def get_score_backend(name: str) -> Callable:
    try:
        return SCORE_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown score_impl {name!r}; expected one of "
            f"{sorted(SCORE_BACKENDS)}") from None


# -- shard-state transports ---------------------------------------------------


@runtime_checkable
class ShardGather(Protocol):
    """Reduces per-worker (Q, k) heap states to one merged state.

    ``merge`` must return the *same* merged ranking on every worker
    (allgather semantics), and must merge rank states in rank order so
    tie-breaking is deterministic across transports.
    """

    def merge(self, heap: FastResultHeapq,
              worker_index: int) -> FastResultHeapq: ...


class ProcessAllGather:
    """Real multi-node transport over ``jax.distributed``.

    Every process contributes its local (Q, k) state through
    ``multihost_utils.process_allgather``; each then merges all W states
    in rank order — the O(Q·k·W) cross-node reduction.  The merged heap
    keeps the local heap's impl so this transport is interchangeable
    with ``launch.distributed.InMemoryAllGather``.
    """

    def merge(self, heap: FastResultHeapq,
              worker_index: int) -> FastResultHeapq:
        from jax.experimental import multihost_utils
        vals, ids = heap.finalize()
        all_v = np.asarray(
            multihost_utils.process_allgather(jnp.asarray(vals)))
        all_i = np.asarray(
            multihost_utils.process_allgather(jnp.asarray(ids)))
        merged = FastResultHeapq(vals.shape[0], heap.k, impl=heap.impl)
        for p in range(all_v.shape[0]):
            merged.merge_arrays(all_v[p], all_i[p])
        return merged

    def exchange_observations(self, worker_index: int, items: int,
                              seconds: float) -> list[tuple[int, int,
                                                            float]]:
        """Allgather every worker's round observation so each process's
        local ``FairSharder`` replica commits the identical round (a
        process reporting only its own rank would leave the round
        incomplete forever and freeze the EMA)."""
        from jax.experimental import multihost_utils
        mine = jnp.asarray([float(worker_index), float(items), seconds],
                           jnp.float32)
        everyone = np.asarray(multihost_utils.process_allgather(mine))
        return [(int(rank), int(n), float(secs))
                for rank, n, secs in everyone]


class MergeFnGather:
    """Adapter for a plain ``heap -> heap`` merge callable (the
    evaluator's legacy ``shard_merge_fn`` injection point)."""

    def __init__(self, fn: Callable[[FastResultHeapq], FastResultHeapq]):
        self.fn = fn

    def merge(self, heap: FastResultHeapq,
              worker_index: int) -> FastResultHeapq:
        return self.fn(heap)


# -- superchunk autotune ------------------------------------------------------
#
# The superchunk executor folds S streamed chunks into ONE jitted
# lax.scan dispatch (kernels.ops.superchunk_update).  How large S should
# be is a machine property: the ratio of per-dispatch overhead (Python +
# jit call + executable launch) to per-chunk device compute.  We measure
# both once per (shape, backend) key with a quick warmup — a no-op jit
# round-trip for the dispatch cost, a single-step scan for the per-chunk
# cost — and size S so dispatch overhead is ~5% of superchunk work.

_NOOP_DISPATCH_S: float | None = None
_AUTOTUNE_CACHE: dict[tuple, int] = {}


def _noop_dispatch_seconds() -> float:
    """Per-call overhead of dispatching a trivial jitted function."""
    global _NOOP_DISPATCH_S
    if _NOOP_DISPATCH_S is None:
        f = jax.jit(lambda x: x + 1)
        x = jnp.zeros((8, 8), jnp.float32)
        f(x).block_until_ready()
        best = math.inf
        for _ in range(5):
            t0 = time.perf_counter()
            f(x).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        _NOOP_DISPATCH_S = best
    return _NOOP_DISPATCH_S


def autotune_superchunk_size(n_queries: int, dim: int, chunk_size: int,
                             k: int, score_impl: str, merge_impl: str,
                             *, overhead_target: float = 0.05,
                             floor: int = 8, ceiling: int = 256) -> int:
    """Pick S so per-superchunk dispatch overhead is ~``overhead_target``
    of its device work.  Cached per (shape, backend) key; the warmup
    costs one small scan compile + a few microsecond-scale timed calls.
    """
    key = (n_queries, dim, chunk_size, k, score_impl, merge_impl,
           jax.default_backend())
    if key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key]
    from repro.kernels import ops as kops
    rows = n_queries + (-n_queries) % 8
    # deterministic synthetic data (values are irrelevant to the timing)
    q = (jnp.arange(max(rows * dim, 1), dtype=jnp.float32)
         .reshape(rows, dim) % 7.0)
    tile = (jnp.arange(chunk_size * dim, dtype=jnp.float32)
            .reshape(1, chunk_size, dim) % 5.0)
    offs = jnp.zeros(1, jnp.int32)
    nvs = jnp.full(1, chunk_size, jnp.int32)

    def one_step(v, i):
        return kops.superchunk_update(v, i, q, tile, offs, nvs, k=k,
                                      score=score_impl, merge=merge_impl)

    v = jnp.full((rows, k), -jnp.inf, jnp.float32)
    i = jnp.full((rows, k), -1, jnp.int32)
    v, i = one_step(v, i)                     # compile
    jax.block_until_ready((v, i))
    per_chunk = math.inf
    for _ in range(3):
        t0 = time.perf_counter()
        v, i = one_step(v, i)
        jax.block_until_ready((v, i))
        per_chunk = min(per_chunk, time.perf_counter() - t0)
    dispatch = _noop_dispatch_seconds()
    compute = max(per_chunk - dispatch, 1e-7)
    s = int(math.ceil(dispatch / (overhead_target * compute)))
    s = max(floor, min(ceiling, s))
    _AUTOTUNE_CACHE[key] = s
    return s


# -- the driver ---------------------------------------------------------------

# legacy pull contract: (lo, hi) -> embeddings.  Objects exposing
# ``open_slice(lo, hi, chunk_size)`` (chunk sources, e.g. the bucketed
# encode pipeline) and :class:`ResidentRows` are accepted wherever a
# ChunkLoader is.
ChunkLoader = Callable[[int, int], "np.ndarray | jax.Array"]


class ResidentRows:
    """A corpus held whole on the device as one float32 row array.

    ``rows`` is ``(n + pad, d)``: the ``n`` corpus rows, then one
    lane-aligned chunk of zero rows (padded on the host, so the device
    holds one copy) so a scan step's fixed-size read starting at any
    chunk offset stays inside the array.  The driver detects the
    ``rows`` device array and scans it in place (one ``dynamic_slice``
    per step inside the jitted scan); calling the object keeps the
    legacy ``(lo, hi) -> rows[lo:hi]`` contract for every other
    consumer.
    """

    __slots__ = ("rows", "n")

    def __init__(self, host_rows: np.ndarray, chunk_size: int,
                 device=None):
        from repro.kernels.ops import scan_chunk_rows
        host_rows = np.asarray(host_rows, np.float32)
        pad = np.zeros((scan_chunk_rows(chunk_size, interpret=False),
                        host_rows.shape[1]), np.float32)
        self.rows = jax.device_put(np.concatenate([host_rows, pad]), device)
        self.n = host_rows.shape[0]

    def __call__(self, lo: int, hi: int) -> jax.Array:
        return self.rows[lo:hi]


class ShardedSearchDriver:
    """One worker's view of a W-worker sharded dense search.

    Parameters
    ----------
    n_workers / worker_index : cluster shape and this worker's rank.
    sharder : shared :class:`FairSharder`; pass the *same* instance to
        all drivers of a cluster so the throughput EMA state is global.
    score_impl / heap_impl : backend names (see ``SCORE_BACKENDS`` and
        ``FastResultHeapq``).
    chunk_size : corpus items per streamed chunk.
    prefetch : double-buffer chunk loads (chunk ``i+1``'s cache-read /
        encode / h2d overlaps chunk ``i``'s scoring).  Never changes
        results — chunks are still scored in order — only overlap.
    gather : :class:`ShardGather` transport; ``None`` means local-only
        (the single-worker instantiation).
    superchunk_size : chunks folded into one jitted scan dispatch
        (device backends only).  ``0`` = autotune from a warmup
        measurement; ``1`` = disable (one dispatch per chunk, the
        pre-superchunk behavior); ``N > 1`` = fixed.  Host backends
        (``score_impl='numpy'`` / ``heap_impl='python'``) always stream
        per-chunk.  Never changes results — the scan replays the exact
        per-chunk merge sequence on device.
    superchunk_max_mb : cap on the stacked (S, C, d) tile so autotuned
        or configured S can't blow device memory.  A device-resident
        corpus (a loader with a ``rows`` device array, e.g.
        :class:`ResidentRows`) is scanned in place with no tile, so the
        cap does not bound its S.
    fault_injector : optional :class:`repro.core.faults.FaultInjector`
        consulted at the chunk-load and gather fault points (chaos
        tests, ``serve --chaos``).  ``None`` = no injection.
    round_deadline_s / max_shard_retries / retry_backoff_s : recovery
        knobs forwarded to a resilient gather (one exposing
        ``merge_resilient``): how long a round waits for a silent
        worker before reassigning its shard, how many rescore attempts
        an orphaned shard gets, and the exponential-backoff base
        between attempts.  Ignored by barrier-style transports.
    """

    def __init__(self, *, n_workers: int = 1, worker_index: int = 0,
                 sharder: FairSharder | None = None,
                 score_impl: str = "jax", heap_impl: str = "jax",
                 chunk_size: int = 32, prefetch: bool = True,
                 gather: ShardGather | None = None,
                 superchunk_size: int = 0, superchunk_max_mb: int = 64,
                 fault_injector: FaultInjector | None = None,
                 round_deadline_s: float = 30.0,
                 max_shard_retries: int = 2,
                 retry_backoff_s: float = 0.05):
        if not 0 <= worker_index < n_workers:
            raise ValueError(
                f"worker_index {worker_index} outside [0, {n_workers})")
        if superchunk_size < 0:
            raise ValueError(
                f"superchunk_size must be >= 0, got {superchunk_size}")
        self.n_workers = n_workers
        self.worker_index = worker_index
        self.sharder = sharder if sharder is not None else FairSharder(
            n_workers)
        self.score_impl = score_impl
        self.heap_impl = heap_impl
        self.chunk_size = chunk_size
        self.prefetch = prefetch
        self.gather = gather
        self.superchunk_size = superchunk_size
        self.superchunk_max_mb = superchunk_max_mb
        self.fault_injector = fault_injector
        self.round_deadline_s = round_deadline_s
        self.max_shard_retries = max_shard_retries
        self.retry_backoff_s = retry_backoff_s
        # per-round observability (bench_multinode, serve logging)
        self.stats: dict = {}
        # round counter for the single-worker path (W>1 uses the
        # sharder-global round from FairSharder.acquire)
        self._local_round = 0
        # lazy single-thread executor for search_async reduces; one
        # thread serializes merges in submission order (determinism)
        self._reduce_pool: ThreadPoolExecutor | None = None

    # -- coordinator ----------------------------------------------------------
    def partition(self, n_docs) -> list[tuple[int, int]]:
        """All workers' ``[lo, hi)`` corpus bounds for this round.

        ``n_docs`` is a document count or any sized corpus object — in
        particular a lazy ``repro.data.views.DatasetView`` composition,
        which is partitioned positionally without ever materializing it.
        A sized object may expose ``partition_boundaries`` (sorted cut
        points covering ``[0, len)``, e.g. the IVF search space's
        cluster edges); shard cuts then snap to those boundaries so
        every worker's slice stays a run of whole clusters.
        """
        boundaries = getattr(n_docs, "partition_boundaries", None)
        if not isinstance(n_docs, (int, np.integer)):
            n_docs = len(n_docs)
        return self.sharder.bounds(int(n_docs), boundaries)

    # -- worker ---------------------------------------------------------------
    def _pipelined_chunks(self, lo: int, hi: int, load_chunk: ChunkLoader):
        """Yield ``(offset, embeddings)`` for this worker's slice.

        ``load_chunk`` is either the legacy ``(lo, hi) -> embeddings``
        callable, or a **chunk source** — an object with
        ``open_slice(lo, hi, chunk_size)`` returning an ordered
        ``(offset, embeddings)`` iterator (e.g.
        ``core.encode_pipeline.PipelineChunkSource``).  A source runs
        its own host/device overlap (background tokenize, bucketed
        encode), so the driver's prefetch thread stands down for it.

        With ``prefetch`` on (legacy callables), a single loader thread
        keeps exactly one chunk in flight ahead of scoring (double
        buffering): while the caller scores chunk ``i``, chunk ``i+1``
        is being cache-read / encoded / copied to device.  Loads stay
        serialized with each other (one loader thread), so cache writes
        need no ordering logic here.
        """
        open_slice = getattr(load_chunk, "open_slice", None)
        if open_slice is not None:
            if hi > lo:
                yield from open_slice(lo, hi, self.chunk_size)
            return
        bounds = [(off, min(off + self.chunk_size, hi))
                  for off in range(lo, hi, self.chunk_size)]

        def load(off: int, end: int):
            with tracing.span("trove.search.load"):
                return load_chunk(off, end)

        if not self.prefetch or len(bounds) <= 1:
            for off, end in bounds:
                yield off, load(off, end)
            return
        with ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="chunk-prefetch") as ex:
            fut = ex.submit(load, *bounds[0])
            for i, (off, _) in enumerate(bounds):
                with tracing.span("trove.search.wait"):
                    embs = fut.result()
                if i + 1 < len(bounds):
                    fut = ex.submit(load, *bounds[i + 1])
                yield off, embs

    # -- superchunk scan executor ---------------------------------------------
    def _resolve_superchunk_size(self, n_queries: int, dim: int,
                                 k: int, *, tiled: bool = True) -> int:
        """Effective S for this search (config / autotune / memory cap).
        ``tiled=False`` (the resident scan, which uploads no tile) skips
        the memory cap."""
        if self.superchunk_size == 1:
            return 1
        merge = "pallas" if self.heap_impl == "pallas" else "jax"
        s = (self.superchunk_size if self.superchunk_size > 1 else
             autotune_superchunk_size(n_queries, dim, self.chunk_size, k,
                                      self.score_impl, merge))
        if not tiled:
            return s
        # budget what actually uploads: compiled backends lane-align the
        # chunk axis to 128 (see superchunk_update), so a chunk_size=32
        # tile occupies 4x its nominal bytes on device
        from repro.kernels.ops import scan_chunk_rows
        c = scan_chunk_rows(self.chunk_size)
        tile_bytes = max(1, c * max(dim, 1) * 4)
        cap = max(1, (self.superchunk_max_mb << 20) // tile_bytes)
        return max(1, min(s, cap))

    def _chunk_iter(self, lo: int, hi: int, load_chunk: ChunkLoader,
                    round_no: int, phase: str):
        """The streamed chunk iterator, with the chunk-level fault point
        (injected crashes / stalls) applied before each chunk is
        scored."""
        chunks = self._pipelined_chunks(lo, hi, load_chunk)
        if self.fault_injector is None:
            return chunks

        def faulty():
            try:
                for ci, (off, embs) in enumerate(chunks):
                    self.fault_injector.on_chunk(self.worker_index,
                                                 round_no, ci, phase)
                    yield off, embs
            finally:
                # an injected crash abandons the iteration mid-slice;
                # close the pipeline generator NOW so its prefetch
                # executor shuts down instead of lingering until GC
                close = getattr(chunks, "close", None)
                if close is not None:
                    close()
        return faulty()

    @staticmethod
    def _scan_state(q_emb, topk: int):
        """The scan executors' inputs: the queries padded to a multiple
        of 8 rows and an empty (Q, k) state, on the queries' device (the
        worker's chip; host queries: the default device), plus that
        device."""
        n_q = q_emb.shape[0]
        dev = (next(iter(q_emb.devices())) if isinstance(q_emb, jax.Array)
               else None)
        pad_rows = (-n_q) % 8
        qp = jax.device_put(q_emb, dev)
        if pad_rows:
            qp = jnp.pad(qp, ((0, pad_rows), (0, 0)))
        state_v = jnp.full((n_q + pad_rows, topk), -jnp.inf, jnp.float32,
                           device=dev)
        state_i = jnp.full((n_q + pad_rows, topk), -1, jnp.int32,
                           device=dev)
        return qp, state_v, state_i, dev

    def _search_superchunk(self, q_emb, heap: FastResultHeapq, chunks,
                           topk: int, s: int) -> int:
        """Stream the slice through one-dispatch-per-superchunk scans.

        Accumulates S loaded chunks (prefetch thread unchanged), stacks
        them into an (S, C, d) tile — ONE host->device upload per
        superchunk when chunks arrive as numpy — and folds the tile into
        the donated device-resident (Q, k) state via a single jitted
        lax.scan (``kernels.ops.superchunk_update``).  Returns the
        number of scan dispatches.
        """
        from repro.kernels import ops as kops
        n_q, dim = q_emb.shape
        c = self.chunk_size
        merge = "pallas" if self.heap_impl == "pallas" else "jax"
        qp, state_v, state_i, dev = self._scan_state(q_emb, topk)
        dispatches = 0

        def flush(buf):
            nonlocal state_v, state_i, dispatches
            offs = np.zeros(s, np.int32)
            nvs = np.zeros(s, np.int32)
            for si, (off, embs) in enumerate(buf):
                offs[si] = off
                nvs[si] = embs.shape[0]
            with tracing.span("trove.search.tile"):
                if all(isinstance(e, np.ndarray) for _, e in buf):
                    host = np.zeros((s, c, dim), np.float32)
                    for si, (_, embs) in enumerate(buf):
                        host[si, :embs.shape[0]] = embs
                    tile = jax.device_put(host, dev)
                else:       # device-resident chunks (online encode path)
                    parts = []
                    for _, embs in buf:
                        e = jnp.asarray(embs, jnp.float32)
                        if e.shape[0] < c:
                            e = jnp.pad(e, ((0, c - e.shape[0]), (0, 0)))
                        parts.append(e)
                    parts += [jnp.zeros((c, dim), jnp.float32, device=dev)
                              ] * (s - len(buf))
                    tile = jnp.stack(parts)
            with tracing.span("trove.search.scan"):
                state_v, state_i = kops.superchunk_update(
                    state_v, state_i, qp, tile, offs, nvs, k=topk,
                    score=self.score_impl, merge=merge)
            dispatches += 1

        buf: list = []
        for off, embs in chunks:
            buf.append((off, embs))
            if len(buf) == s:
                flush(buf)
                buf = []
        if buf:
            flush(buf)
        heap.adopt_state(state_v[:n_q], state_i[:n_q])
        return dispatches

    def _resident_rows(self, load_chunk: ChunkLoader, lo: int, hi: int):
        """The device row array to scan in place for ``[lo, hi)``, or
        ``None`` to stream.  The loader qualifies when its ``rows`` is a
        device array long enough for the last chunk's fixed-size scan
        read (:class:`ResidentRows` pads for exactly that)."""
        rows = getattr(load_chunk, "rows", None)
        if not isinstance(rows, jax.Array) or rows.ndim != 2:
            return None
        from repro.kernels.ops import scan_chunk_rows
        last = lo + (hi - lo - 1) // self.chunk_size * self.chunk_size
        if last + scan_chunk_rows(self.chunk_size) > rows.shape[0]:
            return None
        return rows

    def _search_resident(self, q_emb, heap: FastResultHeapq, rows,
                         lo: int, hi: int, topk: int, s: int,
                         round_no: int, phase: str) -> int:
        """Scan ``[lo, hi)`` of a device-resident corpus in place.

        Chunk boundaries are ``range(lo, hi, chunk_size)``, grouped S to
        a dispatch exactly as the streamed executor groups them; each
        dispatch hands only the group's (S,) offsets and valid counts to
        ``kernels.ops.superchunk_update``, whose resident scan reads
        every chunk out of ``rows`` on the device.  Nothing is sliced,
        padded or stacked per chunk.  The chunk-level fault point fires
        once per chunk, in order, before the dispatch that scans it.
        Returns the number of scan dispatches.
        """
        from repro.kernels import ops as kops
        n_q = q_emb.shape[0]
        c = self.chunk_size
        merge = "pallas" if self.heap_impl == "pallas" else "jax"
        qp, state_v, state_i, _ = self._scan_state(q_emb, topk)
        starts = np.arange(lo, hi, c, dtype=np.int64)
        dispatches = 0
        for g in range(0, len(starts), s):
            group = starts[g:g + s]
            offs = np.zeros(s, np.int32)
            nvs = np.zeros(s, np.int32)
            offs[:len(group)] = group
            nvs[:len(group)] = np.minimum(group + c, hi) - group
            if self.fault_injector is not None:
                for ci in range(g, g + len(group)):
                    self.fault_injector.on_chunk(self.worker_index,
                                                 round_no, ci, phase)
            with tracing.span("trove.search.scan"):
                state_v, state_i = kops.superchunk_update(
                    state_v, state_i, qp, rows, offs, nvs, k=topk,
                    score=self.score_impl, merge=merge, chunk_size=c)
            dispatches += 1
        heap.adopt_state(state_v[:n_q], state_i[:n_q])
        return dispatches

    def _score_range(self, q_emb, lo: int, hi: int,
                     load_chunk: ChunkLoader, topk: int, round_no: int,
                     phase: str = "load"):
        """Score one ``[lo, hi)`` corpus range into a fresh heap.

        The single scoring implementation for both the worker's own
        shard (``phase='load'``) and a survivor rescoring an orphaned
        sibling shard (``phase='retry'``) — same chunking, same
        executor, same kernels, so a recovered shard's state is bitwise
        what the dead owner would have produced.  Returns ``(heap,
        dispatches, executor, superchunk_size)``.
        """
        n_queries = q_emb.shape[0]
        heap = FastResultHeapq(n_queries, topk, impl=self.heap_impl)
        scan_ok = (self.score_impl in ("jax", "pallas_fused")
                   and self.heap_impl in ("jax", "pallas") and hi > lo)
        rows = self._resident_rows(load_chunk, lo, hi) if scan_ok else None
        s = (self._resolve_superchunk_size(n_queries, q_emb.shape[1], topk,
                                           tiled=rows is None)
             if scan_ok else 1)
        if rows is not None and s > 1:
            executor = "resident"
            dispatches = self._search_resident(q_emb, heap, rows, lo, hi,
                                               topk, s, round_no, phase)
            return heap, dispatches, executor, s
        chunks = self._chunk_iter(lo, hi, load_chunk, round_no, phase)
        if scan_ok and s > 1:
            executor = "superchunk"
            dispatches = self._search_superchunk(q_emb, heap, chunks,
                                                 topk, s)
        else:
            executor = "per_chunk"
            backend = get_score_backend(self.score_impl)
            dispatches = 0
            for off, embs in chunks:
                backend(q_emb, embs, off, heap, topk)
                dispatches += 1
        return heap, dispatches, executor, s

    def _rescore_shard(self, q_emb, lo: int, hi: int,
                       load_chunk: ChunkLoader, topk: int,
                       round_no: int):
        """Recovery callback for the resilient gather: re-run the
        scoring phase over an orphaned sibling shard and return its
        finalized ``(vals, ids)`` state."""
        heap, _, _, _ = self._score_range(q_emb, lo, hi, load_chunk,
                                          topk, round_no, phase="retry")
        return heap.finalize()

    def _score_local(self, q_emb, n_docs, load_chunk: ChunkLoader,
                     topk: int, deadline_s: float | None = None,
                     generation=None):
        """The scoring phase of one round: stream this worker's shard
        slice into a **fresh** local (Q, k) heap and report the round's
        throughput observation.  Every call builds its own
        ``FastResultHeapq`` — donated device buffers are never shared
        between rounds, so a previous round's state may still be merging
        (``search_async``) while this round scores.  Returns ``(heap,
        round_ctx)`` — the context the reduce phase needs for resilient
        merging (round number, the round's full bounds, and a rescore
        callback for orphaned sibling shards)."""
        n_queries = q_emb.shape[0]
        boundaries = getattr(n_docs, "partition_boundaries", None)
        if not isinstance(n_docs, (int, np.integer)):
            n_docs = len(n_docs)
        if self.n_workers > 1:
            # round-versioned partition: with async reduces, workers'
            # scoring phases are no longer barrier-ordered, so a plain
            # bounds() read could straddle an EMA commit and split the
            # corpus differently on different ranks within one round.
            # The sharder-global round number also keys the resilient
            # gather and the round-tagged EMA report — stable even when
            # the caller builds a fresh driver per round (serve).
            # ``generation`` (a prepared corpus's snapshot key) makes
            # the round generation-agreed: a GenerationMismatch raised
            # here propagates before any scoring, the caller re-prepares
            # at the agreed key and retries the same round.
            round_no, bounds = self.sharder.acquire(
                self.worker_index, int(n_docs), boundaries,
                generation=generation)
        else:
            round_no = self._local_round
            self._local_round += 1
            bounds = self.sharder.bounds(int(n_docs), boundaries)
        lo, hi = bounds[self.worker_index]
        n_chunks = -(-max(hi - lo, 0) // self.chunk_size)
        with tracing.span("trove.search.score", round=round_no):
            t0 = time.monotonic()
            heap, dispatches, executor, s = self._score_range(
                q_emb, lo, hi, load_chunk, topk, round_no)
            seconds = time.monotonic() - t0
        # Report the round.  A shared sharder (SimulatedCluster) hears
        # every worker directly; with per-process sharder replicas (real
        # multi-node) the transport must exchange observations or no
        # replica would ever see a complete round.
        reports = [(self.worker_index, hi - lo, seconds)]
        exchange = getattr(self.gather, "exchange_observations", None)
        if self.n_workers > 1 and exchange is not None:
            reports = exchange(self.worker_index, hi - lo, seconds)
        for rank, items, secs in reports:
            self.sharder.update(rank, items, secs, round_no=round_no)
        self.stats = {"lo": lo, "hi": hi, "items": hi - lo,
                      "chunks": n_chunks, "seconds": seconds,
                      "executor": executor, "superchunk_size": s,
                      "dispatch_rounds": dispatches, "round": round_no}
        ctx = {
            "round_no": round_no,
            "bounds": bounds,
            "deadline_s": deadline_s,
            "rescore": lambda rlo, rhi: self._rescore_shard(
                q_emb, rlo, rhi, load_chunk, topk, round_no),
        }
        return heap, ctx

    def _reduce(self, heap: FastResultHeapq, ctx: dict | None = None):
        """The reduce phase: cross-worker gather/merge + host finalize.

        With a resilient gather (one exposing ``merge_resilient``) the
        merge recovers orphaned sibling shards and the result is a
        :class:`~repro.core.faults.SearchOutcome` carrying per-query
        coverage; barrier transports return the plain finalized tuple.
        """
        round_no = ctx["round_no"] if ctx is not None else None
        with tracing.span("trove.search.reduce",
                          round=-1 if round_no is None else round_no):
            if self.n_workers > 1 and self.gather is not None:
                resilient = getattr(self.gather, "merge_resilient", None)
                if resilient is not None and ctx is not None:
                    dropped = False
                    if self.fault_injector is not None:
                        try:
                            self.fault_injector.on_gather(
                                self.worker_index, round_no)
                        except InjectedTransportDrop:
                            # this worker's state is lost in flight; it
                            # stays alive and joins the recovery instead
                            dropped = True
                    vals, ids, coverage = resilient(
                        heap, self.worker_index, round_no, ctx["bounds"],
                        ctx["rescore"], dropped=dropped,
                        round_deadline_s=self.round_deadline_s,
                        max_retries=self.max_shard_retries,
                        backoff_s=self.retry_backoff_s,
                        deadline_s=ctx["deadline_s"])
                    return SearchOutcome(
                        (vals, ids), coverage=coverage,
                        degraded=bool((coverage < 1.0).any()))
                if self.fault_injector is not None and round_no is not None:
                    # a drop against a barrier transport propagates: the
                    # legacy abort-the-round behavior
                    self.fault_injector.on_gather(self.worker_index,
                                                  round_no)
                heap = self.gather.merge(heap, self.worker_index)
            return heap.finalize()

    def search(self, q_emb, n_docs, load_chunk: ChunkLoader,
               topk: int, deadline_s: float | None = None,
               generation=None):
        """Run this worker's encode→score→local-top-k round, then reduce.

        ``n_docs`` may be an int or a sized corpus object (e.g. a lazy
        ``DatasetView``) — the FairSharder partitions it positionally.
        Returns the merged ``(scores (Q, k), positions (Q, k))`` —
        identical on every worker when a gather transport is set.
        Positions are global corpus offsets; ``-1`` marks empty slots.

        ``deadline_s`` (resilient gather only) bounds how long the
        reduce phase may spend recovering orphaned shards; past it the
        round resolves partial — a ``SearchOutcome`` with ``degraded``
        set and per-query ``coverage`` < 1 — instead of raising.

        ``generation`` (optional snapshot key, W > 1 only) pins the
        round to one corpus generation via the sharder's agreement —
        see :meth:`FairSharder.acquire`.  A
        :class:`~repro.core.fair_sharding.GenerationMismatch` raises
        before any scoring or reporting, so the caller can re-prepare
        and call again for the same round.
        """
        heap, ctx = self._score_local(q_emb, n_docs, load_chunk, topk,
                                      deadline_s, generation)
        return self._reduce(heap, ctx)

    def search_async(self, q_emb, n_docs, load_chunk: ChunkLoader,
                     topk: int, deadline_s: float | None = None,
                     generation=None) -> Future:
        """Like :meth:`search`, but the reduce phase (shard gather/merge
        + host finalize) runs on a driver-owned background thread and the
        merged ``(scores, positions)`` come back as a Future.

        The scoring phase still runs synchronously on the caller's
        thread, so by the time this returns the caller may start the
        *next* round's scoring while this round's merge is in flight —
        the round-pipelined regime behind ``launch.serve``'s continuous
        batching and the W=4 scaling-efficiency fix (the per-round
        O(Q·k·W) merge used to serialize after every round's scoring).
        Reduces are serialized in submission order on one thread, so
        results — and the gather transport's rank-order merge — are
        bitwise identical to the synchronous path.
        """
        heap, ctx = self._score_local(q_emb, n_docs, load_chunk, topk,
                                      deadline_s, generation)
        if self._reduce_pool is None:
            self._reduce_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="shard-reduce")
        return self._reduce_pool.submit(self._reduce, heap, ctx)

    def close(self) -> None:
        """Drain and shut down the async-reduce thread (no-op when
        :meth:`search_async` was never used)."""
        if self._reduce_pool is not None:
            self._reduce_pool.shutdown(wait=True)
            self._reduce_pool = None
