"""The corpus scan's share of its roofline: the least time the chip
needs for the scan's work (``work.py``: each scanned row read once at
the storage width, 2 d FLOPs per query and row the algorithm must
score) over the summed device time of the scan programs in the trace.
At serving batch sizes the HBM term bounds."""

from tpubench import trace, work

SCAN_PROGRAMS = r"superchunk_scan"


def read(r):
    seconds = trace.module_seconds(r.tr, SCAN_PROGRAMS)
    rounds = r.counters["rounds"]
    if not seconds or not rounds:
        return None
    d, k = r.cfg["hidden_size"], r.cfg["evaluation"]["topk"]
    width = 2 if r.cfg["storage_dtype"] == "float16" else 4
    nbytes = sum(work.scan_bytes(d, x["items"], x["queries"], k, width)
                 for x in rounds)
    flops = work.scan_flops(d, len(r.out["queries"]) * r.rows_per_query())
    least, _ = work.least_seconds(flops, nbytes, r.peak)
    return r.share(least, seconds)
