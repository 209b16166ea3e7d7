"""Per-layer metrics of a traced run: reduce the trace once, then hand
each metric's own reader the same :class:`Reading`."""

from __future__ import annotations

import math

from tpubench import registry, trace
from tpubench.harness import peaks_for


class Reading:
    """What a metric reader may look at: the run's counters (``out``),
    the reduced trace (``tr``), the configuration, the chip's peaks and
    the work counts of the window."""

    def __init__(self, run, out: dict, tr: trace.Trace, peak: dict):
        self.run = run
        self.out = out
        self.counters = out["counters"]
        self.tr = tr
        self.peak = peak
        self.cfg = run.config
        self.enc = out["system"].enc
        self.window_s = (tr.window[1] - tr.window[0]) / 1e9
        self.busy_s = trace.busy_ns(tr) / 1e9

    def idle_share(self) -> float | None:
        if self.window_s <= 0 or self.busy_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def query_tokens(self) -> list[int]:
        """Real token counts of the window's answered queries."""
        cap = self.cfg["query_max_len"]
        return [min(len(t.split()), cap) for t in self.out["queries"]]

    def rows_per_query(self) -> float:
        """Rows the algorithm must score for one query: all of them for a
        flat index, the query's own ``nprobe`` lists (of the mean list
        length) for IVF."""
        ev, n = self.cfg["evaluation"], self.cfg["num_passages"]
        if ev.get("index_impl", "flat") == "ivf":
            return ev["ivf_nprobe"] * n / ev["ivf_nclusters"]
        return float(n)

    def share(self, least_s: float, actual_s: float) -> float | None:
        if not actual_s or actual_s <= 0 or not math.isfinite(least_s):
            return None
        return 100.0 * least_s / actual_s


def per_layer(run, out: dict) -> dict:
    tr = trace.load(run.trace_dir)
    reading = Reading(run, out, tr, peaks_for(out["device_kind"]))
    metrics = {}
    for m in registry.cell_metrics(run.bench, run.cell, True):
        value = registry.metric_reader(m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return {"metrics": metrics, "busy_s": reading.busy_s,
            "window_s": reading.window_s, "breakdown": trace.breakdown(tr)}

