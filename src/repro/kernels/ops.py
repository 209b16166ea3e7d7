"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) kernels execute in ``interpret=True`` mode; on
TPU they compile via Mosaic.  Wrappers handle padding to hardware-aligned
tiles (lanes = multiples of 128 on TPU) and expose plain array APIs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import topk as _topk


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_axis(x, axis: int, mult: int, fill):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _topk_update_fn(vals, ids, scores, chunk_ids, bq, bc, interpret):
    return _topk.topk_update_pallas(
        vals, ids, scores, chunk_ids, bq=bq, bc=bc, interpret=interpret)


_topk_update_jit = jax.jit(
    _topk_update_fn, static_argnames=("bq", "bc", "interpret"))
# The donated variant: the kernel already aliases the (Q, k) state in
# place (input_output_aliases), so with donation the same device buffers
# stream through every chunk merge with zero copies.  Only for callers
# that own the state and never touch the input arrays again
# (FastResultHeapq, the superchunk scan executor).
_topk_update_jit_donated = jax.jit(
    _topk_update_fn, static_argnames=("bq", "bc", "interpret"),
    donate_argnums=(0, 1))


def topk_update(vals, ids, scores, chunk_ids, *, bq: int = 128,
                bc: int = 512, interpret: bool | None = None,
                donate: bool = False):
    """FastResultHeapq merge: (Q,k) state x (Q,C) chunk -> (Q,k) state.

    ``donate=True`` hands the ``vals``/``ids`` buffers to the kernel
    (zero-copy in-place merge); the caller must not use them afterwards.
    """
    interpret = _default_interpret() if interpret is None else interpret
    q, k = vals.shape
    scores = _pad_axis(jnp.asarray(scores, jnp.float32), 1, 128,
                       _topk.NEG_INF)
    chunk_ids = _pad_axis(jnp.asarray(chunk_ids, jnp.int32), 0, 128, -1)
    vals_p = _pad_axis(jnp.asarray(vals, jnp.float32), 0, 8, _topk.NEG_INF)
    ids_p = _pad_axis(jnp.asarray(ids, jnp.int32), 0, 8, -1)
    fn = _topk_update_jit_donated if donate else _topk_update_jit
    out_v, out_i = fn(
        vals_p, ids_p, _pad_axis(scores, 0, 8, _topk.NEG_INF), chunk_ids,
        bq, min(bc, scores.shape[1]), interpret)
    return out_v[:q], out_i[:q]


@functools.partial(jax.jit,
                   static_argnames=("k", "bq", "bn", "interpret"))
def _fused_jit(queries, docs, id_offset, k, bq, bn, interpret):
    # id_offset is a *traced* scalar consumed inside the kernel (SMEM
    # scalar block): the streaming search passes a different offset per
    # corpus chunk, which must not recompile the kernel each time.
    return _topk.fused_score_topk_pallas(
        queries, docs, k, id_offset=id_offset, bq=bq, bn=bn,
        interpret=interpret)


def fused_score_topk(queries, docs, k: int, *, id_offset=0,
                     bq: int = 128, bn: int = 512,
                     interpret: bool | None = None):
    """Top-k of queries @ docs.T with no HBM score matrix (beyond-paper)."""
    interpret = _default_interpret() if interpret is None else interpret
    q = queries.shape[0]
    if docs.shape[0] == 0:
        # FairSharder legitimately emits empty shards (total_items <
        # n_workers); an empty corpus slice has a well-defined answer —
        # an empty heap state — not a zero-size pallas grid.
        return (jnp.full((q, k), _topk.NEG_INF, jnp.float32),
                jnp.full((q, k), -1, jnp.int32))
    queries_p = _pad_axis(jnp.asarray(queries), 0, 8, 0.0)
    docs = jnp.asarray(docs)
    out_v, out_i = _fused_jit(queries_p, docs,
                              jnp.asarray(id_offset, jnp.int32), k, bq,
                              min(bn, max(docs.shape[0], 8)), interpret)
    return out_v[:q], out_i[:q]


# -- superchunk scan executor -------------------------------------------------
#
# One jitted dispatch folds a whole (S, C, d) superchunk of corpus
# embeddings into the running (Q, k) top-k state: lax.scan over the chunk
# axis runs score + top-k-merge entirely on device, with the heap state
# donated between steps (zero-copy carry) and the per-step id_offset /
# n_valid traced through the scan xs — no recompiles across superchunks
# and no host materialization until finalize().  This is what collapses
# the per-chunk Python + jit-dispatch storm (ShardedSearchDriver pays one
# dispatch per superchunk instead of one per encode_batch_size chunk).
# The resident variant reads each step's chunk straight out of a corpus
# array that already lives on the device (``dynamic_slice`` at the traced
# offset) instead of from a stacked tile, so a corpus held on the chip is
# scanned in place: no per-chunk slice, pad or stack before the dispatch.
# Both variants fold every chunk through the same ``_fold_chunk`` body.


def scan_chunk_rows(chunk_size: int, interpret: bool | None = None) -> int:
    """Rows one scan step reads: the chunk, lane-aligned to 128 rows for
    Mosaic (interpret mode has no alignment constraint).  Rows past the
    chunk's ``n_valid`` are masked."""
    interpret = _default_interpret() if interpret is None else interpret
    return chunk_size if interpret else chunk_size + (-chunk_size) % 128


def _fold_chunk(v, i, queries, docs, off, nv, k, score, merge, interpret):
    """One scan step: score the (C, d) chunk ``docs`` at global offset
    ``off`` (rows at or past ``nv`` masked) and merge it into the (Q, k)
    state ``(v, i)``."""
    c = docs.shape[0]
    if score == "pallas_fused":
        # in-kernel score+top-k: each chunk arrives pre-reduced to
        # (Q, k); merge exactly like FastResultHeapq.merge_arrays
        cand_v, cand_i = _topk.fused_score_topk_pallas(
            queries, docs, k, id_offset=off, n_valid=nv,
            bq=128, bn=min(512, max(c, 8)), interpret=interpret)
        cand_v = jnp.where(jnp.isnan(cand_v), _topk.NEG_INF, cand_v)
        cv = jnp.concatenate([v, cand_v], axis=1)
        ci = jnp.concatenate([i, cand_i], axis=1)
        top_v, pos = jax.lax.top_k(cv, k)
        return top_v, jnp.take_along_axis(ci, pos, axis=1)
    # score == "jax": device matmul, then the heap-impl merge
    scores = jax.lax.dot_general(
        queries, docs, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (Q, C)
    iota = jnp.arange(c, dtype=jnp.int32)
    valid = iota < nv
    scores = jnp.where(valid[None, :], scores, _topk.NEG_INF)
    scores = jnp.where(jnp.isnan(scores), _topk.NEG_INF, scores)
    cids = jnp.where(valid, iota + off, -1)
    if merge == "pallas":
        return _topk.topk_update_pallas(
            v, i, scores, cids, bq=min(128, v.shape[0]),
            bc=min(512, c), interpret=interpret)
    cv = jnp.concatenate([v, scores], axis=1)
    ci = jnp.concatenate(
        [i, jnp.broadcast_to(cids[None, :], scores.shape)], axis=1)
    top_v, pos = jax.lax.top_k(cv, k)
    return top_v, jnp.take_along_axis(ci, pos, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("k", "score", "merge", "interpret"),
                   donate_argnums=(0, 1))
def _superchunk_scan_jit(vals, ids, queries, tile, offsets, n_valids, k,
                         score, merge, interpret):
    def step(carry, xs):
        docs, off, nv = xs
        return _fold_chunk(*carry, queries, docs, off, nv, k, score,
                           merge, interpret), None

    (vals, ids), _ = jax.lax.scan(
        step, (vals, ids), (tile, offsets, n_valids))
    return vals, ids


@functools.partial(jax.jit,
                   static_argnames=("c", "k", "score", "merge",
                                    "interpret"),
                   donate_argnums=(0, 1))
def _superchunk_scan_resident_jit(vals, ids, queries, rows, offsets,
                                  n_valids, c, k, score, merge, interpret):
    def step(carry, xs):
        off, nv = xs
        # rows past n_valid read as zero, as in a streamed tile.  The
        # multiply also pins the dot's operand rounding to this chunk: a
        # bare slice (or select) lets XLA move that convert ahead of the
        # slice and out of the loop, converting the whole corpus on every
        # dispatch (tests/test_tpu_compile.py checks it stays out)
        keep = (jnp.arange(c) < nv).astype(rows.dtype)[:, None]
        docs = jax.lax.dynamic_slice(rows, (off, 0),
                                     (c, rows.shape[1])) * keep
        return _fold_chunk(*carry, queries, docs, off, nv, k, score,
                           merge, interpret), None

    (vals, ids), _ = jax.lax.scan(step, (vals, ids), (offsets, n_valids))
    return vals, ids


def superchunk_update(vals, ids, queries, tile, offsets, n_valids, *,
                      k: int, score: str = "jax", merge: str = "jax",
                      interpret: bool | None = None,
                      chunk_size: int | None = None):
    """Fold S corpus chunks into the (Q, k) state in ONE dispatch.

    ``tile`` is an (S, C, d) superchunk, step ``s`` scoring ``tile[s]``;
    or, with ``chunk_size``, the device-resident float32 corpus (N, d),
    step ``s`` reading ``scan_chunk_rows(chunk_size)`` rows in place at
    ``offsets[s]`` (only read, never copied whole).  Both forms run the
    same step body, so the same chunks give the same state bit for bit.

    ``vals``/``ids`` are DONATED — callers must hold onto the returned
    state instead.  ``offsets``/``n_valids`` are per-step (S,) int32:
    each chunk's global corpus offset and its count of valid rows (tail
    chunks are padded up to C rows; padded steps use ``n_valid == 0``).
    ``score`` selects matmul vs in-kernel fused scoring, ``merge``
    selects the jnp vs pallas top-k merge — mirroring the per-chunk
    backends bit for bit.

    A resident read must end inside the corpus (an out-of-range
    ``dynamic_slice`` would clamp its start and score the wrong rows):
    its owner pads it by ``scan_chunk_rows(chunk_size)`` rows, and an
    offset whose read would run past the end raises here.
    """
    interpret = _default_interpret() if interpret is None else interpret
    assert queries.shape[0] == vals.shape[0], (queries.shape, vals.shape)
    queries = jnp.asarray(queries, jnp.float32)
    n_valids = jnp.asarray(n_valids, jnp.int32)
    if chunk_size is not None:
        c = scan_chunk_rows(chunk_size, interpret)
        offsets = np.asarray(offsets, np.int32)
        if offsets.size and int(offsets.max()) + c > tile.shape[0]:
            raise ValueError(
                f"a {c}-row read at offset {int(offsets.max())} runs past "
                f"the {tile.shape[0]} resident rows")
        return _superchunk_scan_resident_jit(
            vals, ids, queries, tile, jnp.asarray(offsets), n_valids, c, k,
            score, merge, interpret)
    tile = jnp.asarray(tile, jnp.float32)
    if not interpret:
        # lane-align the chunk axis for Mosaic (scan_chunk_rows); padded
        # rows are masked by n_valid
        tile = _pad_axis(tile, 1, 128, 0.0)
    return _superchunk_scan_jit(
        vals, ids, queries, tile, jnp.asarray(offsets, jnp.int32),
        n_valids, k, score, merge, interpret)
