"""FastResultHeapq: streaming top-k tracking with matrix ops (paper §3.5).

Replaces Python's ``heapq`` (the paper's 16x-600x baseline) with a fixed
(Q, k) buffer merged against each incoming score chunk via batched top-k.
Three interchangeable impls:

  * ``python``  — the heapq baseline the paper benchmarks against
  * ``jax``     — jnp concat + lax.top_k (the paper's torch analogue)
  * ``pallas``  — fused streaming-merge TPU kernel (repro.kernels)

All return identical results (tested); the evaluator selects via
``EvaluationArguments.heap_impl``.
"""

from __future__ import annotations

import heapq
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = jnp.float32(-jnp.inf)


@partial(jax.jit, static_argnames=("k",), donate_argnums=(0, 1))
def _update_jax(vals, ids, scores, chunk_ids, k: int):
    # NaN scores mean "never retrieve" (see class docstring): sanitize to
    # -inf so lax.top_k's NaN ordering can't differ from the other impls
    scores = jnp.where(jnp.isnan(scores), NEG_INF, scores)
    cand_v = jnp.concatenate([vals, scores.astype(jnp.float32)], axis=1)
    cand_i = jnp.concatenate(
        [ids, jnp.broadcast_to(chunk_ids[None, :],
                               scores.shape).astype(ids.dtype)], axis=1)
    top_v, pos = jax.lax.top_k(cand_v, k)
    top_i = jnp.take_along_axis(cand_i, pos, axis=1)
    return top_v, top_i


@partial(jax.jit, static_argnames=("k",), donate_argnums=(0, 1))
def _merge_arrays_jax(vals, ids, cand_v, cand_i, k: int):
    # one dispatch per merge instead of an eager where/concat/top_k/take
    # op storm; the running state buffers are donated (selection ops
    # only — no float arithmetic — so jit changes nothing numerically)
    cand_v = jnp.where(jnp.isnan(cand_v), NEG_INF, cand_v)
    cv = jnp.concatenate([vals, cand_v], axis=1)
    ci = jnp.concatenate([ids, cand_i], axis=1)
    top_v, pos = jax.lax.top_k(cv, k)
    return top_v, jnp.take_along_axis(ci, pos, axis=1)


@jax.jit
def _finalize_sort(vals, ids):
    order = jnp.argsort(-vals, axis=1)
    return (jnp.take_along_axis(vals, order, 1),
            jnp.take_along_axis(ids, order, 1))


class FastResultHeapq:
    """Tracks top-k (score, doc_id) per query over streamed score chunks.

    Device-side ids are int32 *positions* (e.g. global corpus offsets);
    callers map positions back to raw/hashed ids on the host.  (JAX
    defaults to 32-bit — storing 63-bit id hashes on device would
    silently truncate.)

    NaN and -inf scores are defined to mean "never retrieve": such
    candidates never surface a doc id, in any impl.  (NaN: Python
    float/tuple comparisons and lax.top_k order NaN differently; -inf:
    the device impls can't distinguish a real -inf candidate from an
    empty -inf/-1 buffer slot, so the python impl drops them too —
    without this the impls would diverge on under-filled heaps.)
    """

    HEAP_IMPLS = ("python", "jax", "pallas")

    def __init__(self, n_queries: int, k: int, impl: str = "jax"):
        # fail at construction, not deep in a search round: an unknown
        # impl used to silently run the jax path, and k < 1 only
        # surfaced as a shape error inside lax.top_k
        if impl not in self.HEAP_IMPLS:
            raise ValueError(f"unknown heap impl {impl!r}; expected one "
                             f"of {list(self.HEAP_IMPLS)}")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if n_queries < 0:
            raise ValueError(f"n_queries must be >= 0, got {n_queries}")
        self.k = k
        self.n_queries = n_queries
        self.impl = impl
        if impl == "python":
            self._heaps: list[list[tuple[float, int]]] = [
                [] for _ in range(n_queries)]
        else:
            # uncommitted: the first update moves them to the scores'
            # device
            self.vals = jnp.full((n_queries, k), NEG_INF, jnp.float32)
            self.ids = jnp.full((n_queries, k), -1, jnp.int32)

    def update(self, scores, chunk_ids):
        """scores (Q, C) for C docs with ids chunk_ids (C,)."""
        if self.impl == "python":
            s = np.asarray(scores)
            cid = np.asarray(chunk_ids)
            for q in range(self.n_queries):
                h = self._heaps[q]
                for c in range(s.shape[1]):
                    sc = float(s[q, c])
                    if sc != sc or sc == -np.inf:    # never retrieve
                        continue
                    item = (sc, int(cid[c]))
                    if len(h) < self.k:
                        heapq.heappush(h, item)
                    elif item > h[0]:
                        heapq.heapreplace(h, item)
            return
        if self.impl == "pallas":
            from repro.kernels import ops as kops
            scores = jnp.asarray(scores)
            scores = jnp.where(jnp.isnan(scores), NEG_INF, scores)
            # the heap owns its state arrays and replaces them right
            # here, so the kernel may merge into the donated buffers
            self.vals, self.ids = kops.topk_update(
                self.vals, self.ids, scores, jnp.asarray(chunk_ids),
                donate=True)
            return
        self.vals, self.ids = _update_jax(
            self.vals, self.ids, jnp.asarray(scores),
            jnp.asarray(chunk_ids), self.k)

    def merge_arrays(self, vals, ids):
        """Merge per-query candidate arrays vals (Q, m), ids (Q, m).

        The entry point for fused score+top-k kernel output: each corpus
        chunk already arrives reduced to (Q, k') on device, and merges
        here without constructing a throwaway heap object.  ``ids`` < 0
        marks empty slots (vals must be -inf there).
        """
        if self.impl == "python":
            v = np.asarray(vals)
            i = np.asarray(ids)
            for q in range(self.n_queries):
                h = self._heaps[q]
                for c in range(v.shape[1]):
                    sc = float(v[q, c])
                    if i[q, c] < 0 or sc != sc or sc == -np.inf:
                        continue
                    item = (sc, int(i[q, c]))
                    if len(h) < self.k:
                        heapq.heappush(h, item)
                    elif item > h[0]:
                        heapq.heapreplace(h, item)
            return
        self.vals, self.ids = _merge_arrays_jax(
            self.vals, self.ids, jnp.asarray(vals, jnp.float32),
            jnp.asarray(ids).astype(self.ids.dtype), self.k)

    def merge(self, other: "FastResultHeapq"):
        """Merge another heap's state (cross-shard top-k reduction)."""
        self.merge_arrays(*other.finalize())

    def adopt_state(self, vals, ids):
        """Install a device-resident (Q, k) state wholesale — the hand-off
        point for the superchunk scan executor, whose donated scan carry
        IS the heap state.  Device impls only."""
        assert self.impl != "python", "python impl has no array state"
        assert vals.shape == (self.n_queries, self.k), vals.shape
        self.vals = jnp.asarray(vals, jnp.float32)
        self.ids = jnp.asarray(ids, jnp.int32)

    def finalize_device(self):
        """Device-side sorted finalize: -> (vals (Q,k) desc, ids int32)
        as device arrays — no host transfer (device impls only; callers
        that need numpy use :meth:`finalize`)."""
        assert self.impl != "python", "python impl finalizes on host"
        return _finalize_sort(self.vals, self.ids)

    def finalize(self):
        """-> (scores (Q,k) desc-sorted, doc_ids (Q,k)); -1 id == empty."""
        if self.impl == "python":
            vals = np.full((self.n_queries, self.k), -np.inf, np.float32)
            ids = np.full((self.n_queries, self.k), -1, np.int64)
            for q, h in enumerate(self._heaps):
                for j, (s, d) in enumerate(sorted(h, reverse=True)):
                    vals[q, j] = s
                    ids[q, j] = d
            return vals, ids
        vals, ids = self.finalize_device()
        return np.asarray(vals), np.asarray(ids, dtype=np.int64)
