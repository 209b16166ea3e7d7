"""Pallas TPU kernels: streaming top-k (FastResultHeapq) + fused score+top-k.

TPU adaptation of the paper's FastResultHeapq (DESIGN.md §2.1): the
running (Q, k) top-k buffer lives in a *revisited* output block (aliased
with the input state), and each grid step merges one score tile from
VMEM.  ``fused_score_topk`` additionally produces the score tile on the
MXU from (Q,d)x(d,N) inside the kernel, so the (Q,N) score matrix never
exists in HBM — the HBM-traffic term of retrieval drops from O(Q*N) to
O(N*d + Q*k).

Selection uses a VPU-only iterative max+mask loop (no ``lax.top_k`` /
``sort`` dependency, which Mosaic does not lower): per selected rank we
compute a row max, locate its first occurrence via iota-min, emit, and
mask.  k is a compile-time constant; cost O(k*(k+bc)) VPU ops per tile.

Tiling: bq rows x (k + bc) candidate lanes; defaults keep the working set
(bq*(k+bc)*8B) well under VMEM and lane-align k, bc to 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float("-inf")


def _select_topk_into(out_v_ref, out_i_ref, cand_v, cand_i, k: int):
    """Iteratively select the k largest (value, id) pairs of cand into refs.

    The (bq, k) results ride the loop carry and each ref is written once
    after the loop: Mosaic refuses a store at a dynamic lane offset
    (``ref[:, pl.ds(j, 1)]``), so rank ``j`` fills its column by a select
    against a lane iota instead.
    """
    bq = cand_v.shape[0]
    rank = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)

    def body(j, carry):
        cv, out_v, out_i = carry
        m = jnp.max(cv, axis=1)                                   # (bq,)
        iota = jax.lax.broadcasted_iota(jnp.int32, cv.shape, 1)
        at_max = cv == m[:, None]
        first = jnp.min(jnp.where(at_max, iota, cv.shape[1]), axis=1)
        onehot = iota == first[:, None]
        sel_id = jnp.max(jnp.where(onehot, cand_i, -1), axis=1)
        # -inf means "empty / never retrieve": emit -1, not the id.  The
        # NEG_INF mask below can't distinguish an already-selected
        # position from a genuinely empty one — without this, once the
        # running max hits -inf the first selected position would be
        # re-picked and re-emit its real id (duplicate ids in the tail).
        sel_id = jnp.where(m == NEG_INF, -1, sel_id)
        col = rank == j
        out_v = jnp.where(col, m[:, None], out_v)
        out_i = jnp.where(col, sel_id[:, None], out_i)
        return jnp.where(onehot, NEG_INF, cv), out_v, out_i

    _, out_v, out_i = jax.lax.fori_loop(
        0, k, body, (cand_v, jnp.full((bq, k), NEG_INF, jnp.float32),
                     jnp.full((bq, k), -1, jnp.int32)))
    out_v_ref[...] = out_v
    out_i_ref[...] = out_i


def _topk_update_kernel(vals_ref, ids_ref, scores_ref, cids_ref,
                        out_v_ref, out_i_ref, *, k: int):
    # out refs are aliased with (vals, ids): they already hold the running
    # state on the first visit and accumulate across the C-grid axis.
    cand_v = jnp.concatenate(
        [out_v_ref[...], scores_ref[...].astype(jnp.float32)], axis=1)
    tile_ids = jnp.broadcast_to(cids_ref[...], scores_ref.shape
                                ).astype(jnp.int32)
    cand_i = jnp.concatenate([out_i_ref[...], tile_ids], axis=1)
    _select_topk_into(out_v_ref, out_i_ref, cand_v, cand_i, k)


def topk_update_pallas(vals, ids, scores, chunk_ids, *, bq: int = 128,
                       bc: int = 512, interpret: bool = False):
    """Merge scores (Q,C) with ids (C,) into running (vals, ids) (Q,k)."""
    q, k = vals.shape
    c = scores.shape[1]
    bq = min(bq, q)
    bc = min(bc, c)
    grid = (pl.cdiv(q, bq), pl.cdiv(c, bc))
    cids2d = chunk_ids.reshape(1, c).astype(jnp.int32)
    kernel = functools.partial(_topk_update_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(vals.astype(jnp.float32), ids.astype(jnp.int32), scores, cids2d)


def _fused_kernel(scal_ref, q_ref, d_ref, out_v_ref, out_i_ref, *, k: int,
                  bn: int):
    # scal_ref (1, 2) int32 = [id_offset, n_valid]: both *traced* scalars,
    # so a streaming caller (lax.scan over corpus superchunks) can vary
    # the chunk's global offset and its valid-row count per step without
    # recompiling — the scan-carry contract of the superchunk executor.
    j = pl.program_id(1)
    id_offset = scal_ref[0, 0]
    n_valid = scal_ref[0, 1]
    scores = jax.lax.dot_general(
        q_ref[...], d_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                     # (bq, bn)
    base = j * bn
    iota = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + base
    # mask padded doc rows: grid padding (n not a multiple of bn) and
    # caller padding (ragged tail chunks stacked to a fixed tile) alike
    valid = iota < n_valid
    scores = jnp.where(valid, scores, NEG_INF)
    tile_ids = jnp.where(valid, iota + id_offset, -1)

    @pl.when(j == 0)
    def _init():
        out_v_ref[...] = jnp.full_like(out_v_ref, NEG_INF)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    cand_v = jnp.concatenate([out_v_ref[...], scores], axis=1)
    cand_i = jnp.concatenate([out_i_ref[...], tile_ids], axis=1)
    _select_topk_into(out_v_ref, out_i_ref, cand_v, cand_i, k)


def fused_score_topk_pallas(queries, docs, k: int, *, id_offset=0,
                            n_valid=None, bq: int = 128, bn: int = 512,
                            interpret: bool = False):
    """Top-k of queries @ docs.T without materializing the score matrix.

    queries (Q, d), docs (N, d) -> (vals (Q,k) desc, ids int32 (Q,k)).

    ``id_offset`` and ``n_valid`` may be traced int scalars (scan-friendly:
    the superchunk executor varies both per scan step under one jit).
    Docs rows at index >= ``n_valid`` (default N) score -inf / id -1, so a
    ragged tail chunk padded up to a fixed tile shape stays inert.
    """
    q, d = queries.shape
    n = docs.shape[0]
    bq = min(bq, q)
    bn = min(bn, n)
    grid = (pl.cdiv(q, bq), pl.cdiv(n, bn))
    n_valid = n if n_valid is None else jnp.minimum(n_valid, n)
    scal = jnp.stack([jnp.asarray(id_offset, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)]).reshape(1, 2)
    kernel = functools.partial(_fused_kernel, k=k, bn=bn)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, k), jnp.float32),
            jax.ShapeDtypeStruct((q, k), jnp.int32),
        ],
        interpret=interpret,
    )(scal, queries, docs)
