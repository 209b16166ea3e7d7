"""The least work the algorithms need, from shapes.

Counts are of the configuration's algorithm at its stated precision,
whatever implements it: an implementation that does more work (a wider
dtype, padding, a union of probes) cannot read above 100% of a roofline
built from these numbers.
"""

from __future__ import annotations


def encoder_flops(enc: dict, lengths) -> float:
    """Matmul FLOPs of the encoder forward over sequences of the given
    real token counts (padding excluded).  Per layer and token: the
    q/k/v/o projections ``8 d^2`` and the feed-forward ``4 d f``; per
    layer and sequence of ``n`` tokens, causal attention ``2 d n (n+1)``
    (scores and values over the ``n(n+1)/2`` allowed pairs).  The
    embedding lookup, norms and pooling are not counted."""
    d, f, layers = enc["d_model"], enc["d_ff"], enc["n_layers"]
    total = 0.0
    for n in lengths:
        n = int(n)
        total += layers * (n * (8 * d * d + 4 * d * f) + 2 * d * n * (n + 1))
    return total


def scan_flops(d: int, pairs: float) -> float:
    """2 d per (query, row) pair the search must score."""
    return 2.0 * d * pairs


def scan_bytes(d: int, rows: float, queries: int, k: int,
               storage_bytes: int) -> float:
    """Each scanned row read once at the storage width, the queries read
    once (float32), the top-k (score float32, id int32) written once."""
    return rows * d * storage_bytes + queries * d * 4 + queries * k * 8


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Roofline: the larger of compute time at the bf16 peak and memory
    time at the HBM peak, and which one bounds."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "mxu")
