"""A whole run at a tiny size on the CPU, past the harness's look for a
chip: sound, it reads ``correct``; with the timed path broken underneath
in each way the cell can break, ``correct`` comes out false."""

import time

import numpy as np
import pytest

from tpubench import harness
from tpubench.tests import tiny


def run_tiny(cell: str, monkeypatch, tmp_path) -> dict:
    monkeypatch.setattr(harness, "compile_cache_dir",
                        lambda: str(tmp_path / "jax_cache"))
    name = harness.registry.workload(cell)["config"]
    return harness.run_cell(cell, 3000000123, 2.0, False, time.monotonic(),
                            tiny.facts(), config=tiny.config(name),
                            workload=tiny.workload(cell))


# -- serving: the faults a served answer can have -----------------------------


def alter_an_answer(monkeypatch):
    """Each micro-batch's first answer gets another (valid) top id."""
    from repro.core.serving import ServeFrontend
    inner = ServeFrontend._finish

    def finish(self, batch, out):
        ids, scores = (np.array(x) for x in out)
        ids[0, 0] = (ids[0, 0] + 1) % 4096
        while ids[0, 0] in ids[0, 1:]:
            ids[0, 0] = (ids[0, 0] + 1) % 4096
        return inner(self, batch, (ids, scores))

    monkeypatch.setattr(ServeFrontend, "_finish", finish)


def leave_out_half_the_batch(monkeypatch):
    """The backend scores the first half of a micro-batch and hands its
    answers to the second half too."""
    from repro.core.serving import EvaluatorServeBackend
    inner = EvaluatorServeBackend.begin

    def begin(self, texts, topk, **kw):
        half = max(1, len(texts) // 2)
        kept = list(texts[:half]) * 2
        return inner(self, kept[:len(texts)], topk, **kw)

    monkeypatch.setattr(EvaluatorServeBackend, "begin", begin)


def return_state_unchanged(monkeypatch):
    """The scan step hands back the state it was given."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "superchunk_update",
                        lambda vals, ids, *a, **kw: (vals, ids))


@pytest.mark.parametrize("cell", ["flat-serve.poisson", "ivf-serve.poisson"])
def test_sound_serve_run_is_correct(cell, monkeypatch, tmp_path):
    line = run_tiny(cell, monkeypatch, tmp_path)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("fault", [alter_an_answer, leave_out_half_the_batch,
                                   return_state_unchanged])
@pytest.mark.parametrize("cell", ["flat-serve.poisson", "ivf-serve.poisson"])
def test_broken_serve_path_is_not_correct(cell, fault, monkeypatch, tmp_path):
    fault(monkeypatch)
    line = run_tiny(cell, monkeypatch, tmp_path)
    assert not line["correct"], line["checks"]


# -- bulk encode: the faults a written vector can have --------------------------


def alter_a_vector(monkeypatch):
    """Each write stores every vector one row off (a wrong answer where
    it is produced)."""
    from repro.core.embedding_cache import EmbeddingCache
    inner = EmbeddingCache.cache_records
    monkeypatch.setattr(EmbeddingCache, "cache_records",
                        lambda self, ids, v: inner(self, ids,
                                                   np.roll(v, 1, axis=0)))


def leave_out_half_the_vectors(monkeypatch):
    """Only the first half of each call's vectors is written."""
    from repro.core.embedding_cache import EmbeddingCache
    inner = EmbeddingCache.cache_records

    def write(self, ids, v):
        n = len(ids) // 2
        return inner(self, list(ids)[:n], np.asarray(v)[:n])

    monkeypatch.setattr(EmbeddingCache, "cache_records", write)


def encoder_returns_its_input(monkeypatch):
    """The encoder step returns an unchanged (zero) state: every output
    row is the same vector."""
    from repro.core import encode_pipeline
    inner = encode_pipeline.EncodePipeline._encode_window

    def window(self, params, enc, *a, **kw):
        out = inner(self, params, enc, *a, **kw)
        return out * 0 + out[:1]

    monkeypatch.setattr(encode_pipeline.EncodePipeline, "_encode_window",
                        window)


def test_sound_encode_run_is_correct(monkeypatch, tmp_path):
    line = run_tiny("msmarco-encode.bulk", monkeypatch, tmp_path)
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("fault", [alter_a_vector, leave_out_half_the_vectors,
                                   encoder_returns_its_input])
def test_broken_encode_path_is_not_correct(fault, monkeypatch, tmp_path):
    fault(monkeypatch)
    line = run_tiny("msmarco-encode.bulk", monkeypatch, tmp_path)
    assert not line["correct"], line["checks"]
