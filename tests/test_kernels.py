"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
sweeping shapes and dtypes per the spec."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings
from _hypothesis_shim import st

from repro.kernels import ops, ref


def _sorted_pairs(vals, ids):
    order = np.argsort(-np.asarray(vals), axis=1, kind="stable")
    return (np.take_along_axis(np.asarray(vals), order, 1),
            np.take_along_axis(np.asarray(ids), order, 1))


@pytest.mark.parametrize("q,k,c", [(1, 1, 1), (3, 5, 17), (16, 10, 128),
                                   (9, 33, 257), (128, 128, 512)])
def test_topk_update_shapes(q, k, c, rng):
    vals = jnp.asarray(rng.normal(size=(q, k)).astype(np.float32))
    ids = jnp.arange(q * k, dtype=jnp.int32).reshape(q, k)
    scores = jnp.asarray(rng.normal(size=(q, c)).astype(np.float32))
    cids = jnp.arange(10_000, 10_000 + c, dtype=jnp.int32)
    kv, ki = ops.topk_update(vals, ids, scores, cids)
    rv, ri = ref.topk_update_ref(vals, ids, scores, cids)
    kvs, kis = _sorted_pairs(kv, ki)
    rvs, ris = _sorted_pairs(rv, ri)
    np.testing.assert_allclose(kvs, rvs, rtol=1e-6)
    np.testing.assert_array_equal(kis, ris)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("q,d,n,k", [(4, 16, 64, 7), (8, 128, 300, 16)])
def test_fused_score_topk(q, d, n, k, dtype, rng):
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    qs = jnp.asarray(rng.normal(size=(q, d))).astype(dtype)
    ds = jnp.asarray(rng.normal(size=(n, d))).astype(dtype)
    fv, fi = ops.fused_score_topk(qs, ds, k, id_offset=3)
    rv, ri = ref.fused_score_topk_ref(qs, ds, k, id_offset=3)
    np.testing.assert_allclose(np.asarray(fv), np.asarray(rv), rtol=tol,
                               atol=tol)
    # id agreement can differ on near-ties under bf16: check score parity
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(fi), np.asarray(ri))


def test_fused_id_offset_traced_no_recompile(rng):
    """The streaming search passes a different id_offset per corpus chunk;
    offsets must shift ids without triggering a recompile per chunk."""
    qs = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    ds = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    v0, i0 = ops.fused_score_topk(qs, ds, 5, id_offset=0)
    before = ops._fused_jit._cache_size()
    v1, i1 = ops.fused_score_topk(qs, ds, 5, id_offset=1000)
    assert ops._fused_jit._cache_size() == before
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v0))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0) + 1000)


def test_fused_block_sizes(rng):
    qs = jnp.asarray(rng.normal(size=(10, 32)).astype(np.float32))
    ds = jnp.asarray(rng.normal(size=(500, 32)).astype(np.float32))
    base_v, base_i = ref.fused_score_topk_ref(qs, ds, 9)
    for bq, bn in [(4, 64), (8, 128), (16, 512)]:
        fv, fi = ops.fused_score_topk(qs, ds, 9, bq=bq, bn=bn)
        np.testing.assert_allclose(np.asarray(fv), np.asarray(base_v),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(fi), np.asarray(base_i))


@settings(max_examples=15, deadline=None)
@given(q=st.integers(1, 8), d=st.sampled_from([8, 32]),
       n=st.integers(4, 120), k=st.integers(1, 12),
       seed=st.integers(0, 99))
def test_fused_property(q, d, n, k, seed):
    rng = np.random.default_rng(seed)
    k = min(k, n)
    qs = jnp.asarray(rng.normal(size=(q, d)).astype(np.float32))
    ds = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    fv, fi = ops.fused_score_topk(qs, ds, k)
    scores = np.asarray(qs) @ np.asarray(ds).T
    expect = -np.sort(-scores, axis=1)[:, :k]
    np.testing.assert_allclose(np.asarray(fv), expect, rtol=1e-4,
                               atol=1e-5)
    # returned ids index the right scores
    for qi in range(q):
        np.testing.assert_allclose(scores[qi, np.asarray(fi)[qi]],
                                   np.asarray(fv)[qi], rtol=1e-4,
                                   atol=1e-5)

