"""Pure-jnp oracles for the Pallas kernels (correctness references)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def topk_update_ref(vals: jax.Array, ids: jax.Array, scores: jax.Array,
                    chunk_ids: jax.Array):
    """Merge a (Q, C) score chunk into running (Q, k) top-k state.

    vals f32 (Q,k) desc-unordered, ids i32 (Q,k), scores (Q,C),
    chunk_ids i32 (C,).  Returns (vals, ids) of the merged top-k.
    """
    k = vals.shape[1]
    cand_v = jnp.concatenate([vals, scores.astype(jnp.float32)], axis=1)
    cand_i = jnp.concatenate(
        [ids, jnp.broadcast_to(chunk_ids[None, :], scores.shape
                               ).astype(ids.dtype)], axis=1)
    top_v, pos = jax.lax.top_k(cand_v, k)
    return top_v, jnp.take_along_axis(cand_i, pos, axis=1)


def fused_score_topk_ref(queries: jax.Array, docs: jax.Array, k: int,
                         id_offset: int = 0):
    """Exact top-k of queries @ docs.T.

    queries (Q, d), docs (N, d) -> (vals (Q,k) desc, ids i32 (Q,k)).
    """
    scores = jnp.einsum("qd,nd->qn", queries, docs,
                        preferred_element_type=jnp.float32)
    top_v, pos = jax.lax.top_k(scores, k)
    return top_v, (pos + id_offset).astype(jnp.int32)

