"""The whole micro-batch step's share of the chip's bf16 peak: encoder
FLOPs on the window's real query tokens plus 2 d per (query, row) the
algorithm must score, over the summed wall time of the micro-batches
(the benchmark's span from the backend call to its result)."""

from tpubench import work


def read(r):
    walls = r.counters["batch_walls"]
    seconds = sum(t1 - t0 for t0, t1, _ in walls)
    if seconds <= 0:
        return None
    flops = (work.encoder_flops(r.enc, r.query_tokens())
             + work.scan_flops(r.cfg["hidden_size"],
                               len(r.out["queries"]) * r.rows_per_query()))
    return r.share(flops / r.peak["bf16_flops_per_s"], seconds)
