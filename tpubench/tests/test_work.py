"""Work counts from shapes."""

import pytest

from tpubench import harness, work
from tpubench.metrics_io import Reading
from tpubench.tests import tiny

ENC = {"n_layers": 12, "d_model": 768, "d_ff": 3072}


def test_encoder_flops_per_token_and_causal_attention():
    d, f, layers = 768, 3072, 12
    per_token = layers * (8 * d * d + 4 * d * f)
    # one token: projections and FFN, plus attention over itself (2 d * 1 * 2)
    assert work.encoder_flops(ENC, [1]) == per_token + layers * 2 * d * 2
    n = 56
    want = layers * (n * (8 * d * d + 4 * d * f) + 2 * d * n * (n + 1))
    assert work.encoder_flops(ENC, [n, n]) == 2 * want
    # about 9.6 GFLOP for a 56-token passage at BERT-base width
    assert work.encoder_flops(ENC, [56]) == pytest.approx(9.57e9, rel=0.01)


def test_scan_bytes_count_storage_width_once_per_row():
    b = work.scan_bytes(768, rows=1 << 20, queries=32, k=10, storage_bytes=2)
    assert b == (1 << 20) * 768 * 2 + 32 * 768 * 4 + 32 * 10 * 8
    assert work.scan_flops(768, pairs=32 * (1 << 20)) == 2 * 768 * 32 * (1 << 20)


def test_roofline_names_the_bound():
    peak = harness.peaks_for("TPU v5 lite")
    t, bound = work.least_seconds(2 * 768 * 32 * 2**20,
                                  2**20 * 768 * 2, peak)
    assert bound == "hbm" and t == pytest.approx(2**20 * 768 * 2 / 819e9)
    t, bound = work.least_seconds(2 * 768 * 4096 * 2**20, 2**20 * 768 * 2,
                                  peak)
    assert bound == "mxu"


def _reading(cfg):
    r = Reading.__new__(Reading)
    r.cfg = cfg
    return r


def test_ivf_counts_each_querys_own_probe_lists_not_the_union():
    cfg = tiny.config("trove-base.msmarco-1m.ivf4096")
    cfg["num_passages"] = 1 << 20
    cfg["evaluation"].update(ivf_nclusters=4096, ivf_nprobe=32)
    assert _reading(cfg).rows_per_query() == 32 * (1 << 20) / 4096
    flat = tiny.config("trove-base.msmarco-1m.flat")
    flat["num_passages"] = 1 << 20
    assert _reading(flat).rows_per_query() == 1 << 20


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v4")
