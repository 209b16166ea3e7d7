"""Compile rehearsal: the main path's kernels and encoder, compiled for a
described TPU v5e with no chip attached.

Mosaic refuses programs that interpret mode runs happily (a store at a
dynamic lane offset, a slice off the tiling), so these compiles guard the
chip path on every CPU test run.  Nothing here runs a program.  The
topology is described inside a fixture, never at import: only one process
may load the TPU library, and every test worker imports this file.  The
persistent compile cache is off around these compiles (an entry compiled
for a described chip cannot be read back without one).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import topk as _topk

D = 768        # trove-base d_model


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("q,k", [(32, 10), (256, 1000)],
                         ids=["serving", "mining"])
def test_fused_score_topk_compiles(spec, q, k):
    text = _compiled_text(
        lambda qs, ds, off: _topk.fused_score_topk_pallas(
            qs, ds, k, id_offset=off),
        spec((q, D)), spec((65536, D)), spec((), jnp.int32))
    assert "tpu_custom_call" in text


def test_topk_update_compiles(spec):
    q, k, c = 32, 100, 512
    text = _compiled_text(
        _topk.topk_update_pallas, spec((q, k)), spec((q, k), jnp.int32),
        spec((q, c)), spec((c,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("score,merge",
                         [("pallas_fused", "jax"), ("jax", "pallas")])
def test_superchunk_scan_compiles(spec, score, merge):
    q, k, s, c = 32, 10, 8, 512
    text = ops._superchunk_scan_jit.lower(
        spec((q, k)), spec((q, k), jnp.int32), spec((q, D)),
        spec((s, c, D)), spec((s,), jnp.int32), spec((s,), jnp.int32),
        k=k, score=score, merge=merge, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("score,merge",
                         [("pallas_fused", "jax"), ("pallas_fused", "pallas"),
                          ("jax", "pallas"), ("jax", "jax")])
def test_superchunk_scan_resident_compiles(spec, score, merge):
    """The in-place scan of a 2^20-row corpus padded by one lane-aligned
    chunk, at the serving shape: 256 chunks of 256 rows a dispatch.  Its
    scratch stays far below the corpus: the dot's bf16 operand rounding
    happens per chunk inside the loop, never as a whole-corpus convert
    hoisted out of it (1.6 GB of scratch and a full pass per dispatch)."""
    q, k, s, c = 32, 10, 256, 256
    compiled = ops._superchunk_scan_resident_jit.lower(
        spec((q, k)), spec((q, k), jnp.int32), spec((q, D)),
        spec((2 ** 20 + c, D)), spec((s,), jnp.int32),
        spec((s,), jnp.int32), c=c, k=k, score=score, merge=merge,
        interpret=False).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (
        score == "pallas_fused" or merge == "pallas")
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def test_trove_base_encode_compiles(spec, topo):
    from repro.configs import get_arch
    from repro.models.encoder import DefaultEncoder

    cfg = get_arch("trove-base").cfg
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (12, 768, 50304)
    enc = DefaultEncoder(cfg)
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(enc.init_params, jax.random.key(0)))
    b, length = 32, 8          # a serving micro-batch at the query rung
    compiled = jax.jit(
        lambda p, t, m: enc.encode(p, {"tokens": t, "mask": m})).lower(
        params, spec((b, length), jnp.int32),
        spec((b, length), jnp.int32)).compile()
    assert compiled.memory_analysis() is not None
