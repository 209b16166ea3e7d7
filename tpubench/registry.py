"""Find a cell's files by name.

``BENCHMARK.json`` lists the cells and metrics.  Everything that belongs
to one configuration, cell, traffic mix, traffic kind or per-layer
metric lives in a file of its own, named after it:

    tpubench/configs/<config>.json      sizes, settings, source, cuts
    tpubench/workloads/<cell>.json      config, traffic, rate, ...
    tpubench/traffic/<traffic>.json     a mix: its kind and parameters
    tpubench/traffic/<kind>.py          the driver of a kind: run(ctx)
    tpubench/metrics/<metric>.py        read(run) -> float | None

Adding any of these is adding files and entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = CHECKOUT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _safe(name: str) -> str:
    if not name or "/" in name or name.startswith(".") or "\\" in name:
        raise ValueError(f"bad name {name!r}")
    return name


def config(name: str) -> dict:
    return _json(os.path.join(HERE, "configs", _safe(name) + ".json"))


def workload(name: str) -> dict:
    return _json(os.path.join(HERE, "workloads", _safe(name) + ".json"))


def traffic(name: str) -> dict:
    return _json(os.path.join(HERE, "traffic", _safe(name) + ".json"))


def _module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def traffic_driver(kind: str):
    return _module(os.path.join(HERE, "traffic", _safe(kind) + ".py"),
                   f"tpubench_traffic_{kind}")


def metric_reader(name: str):
    return _module(os.path.join(HERE, "metrics", _safe(name) + ".py"),
                   "tpubench_metric_" + name.replace(".", "_"))


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metric entries a cell reports: its end-to-end metrics, or with
    ``trace`` its per-layer ones.  A metric without a ``workloads`` key
    belongs to every cell that reports the metric it moves (per-layer)
    or to every cell (end-to-end)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


def cell_entry(bench: dict, cell: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == cell:
            return w
    raise KeyError(f"no cell named {cell!r} in BENCHMARK.json")
