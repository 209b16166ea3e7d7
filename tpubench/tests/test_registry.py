"""Cells, configurations, traffic and metrics are found by name, and a
new one is added as files and entries only."""

import json
import os
import shutil

import pytest

from tpubench import registry

BENCH = registry.benchmark()


def test_every_entry_resolves_to_its_files():
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(registry.CHECKOUT, c["file"]))
        assert registry.config(c["name"])["source"]
    for w in BENCH["workloads"]:
        cell = registry.workload(w["name"])
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"]
        assert hasattr(registry.traffic_driver(
            registry.traffic(w["traffic"])["kind"]), "run")
    for m in BENCH["per_layer"]:
        assert callable(registry.metric_reader(m["name"]).read)


def test_every_cell_reports_setup_another_e2e_and_a_per_layer_metric():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in registry.cell_metrics(BENCH, w["name"],
                                                         False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = registry.cell_metrics(BENCH, w["name"], True)
        assert layer
        assert all(m["moves"] in e2e for m in layer)


def test_names_are_checked():
    with pytest.raises(ValueError):
        registry.config("../BENCHMARK")


def test_a_new_cell_and_metric_are_files_and_entries_only(tmp_path,
                                                         monkeypatch):
    here = tmp_path / "tpubench"
    shutil.copytree(registry.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    (here / "workloads" / "flat-serve.burst.json").write_text(json.dumps(
        {"config": "trove-base.msmarco-1m.flat", "traffic": "burst",
         "rate_qps": 2.0, "limits": {"unanswered": 0}}))
    (here / "traffic" / "burst.json").write_text(json.dumps(
        {"kind": "serve_open_loop",
         "query_words": {"mu": 1.7, "sigma": 0.4, "min": 2, "max": 20}}))
    (here / "metrics" / "queue.depth.serve.py").write_text(
        "def read(r):\n    return 3.0\n")
    for m in bench["end_to_end"]:
        if "flat-serve.poisson" in m.get("workloads", []):
            m["workloads"].append("flat-serve.burst")
    bench["workloads"].append({"name": "flat-serve.burst",
                               "config": "trove-base.msmarco-1m.flat",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "queue.depth.serve", "unit": "requests",
                               "better": "lower", "source": "program_counter",
                               "layer": "ServeFrontend",
                               "moves": "serve_p95_ms"})
    monkeypatch.setattr(registry, "HERE", str(here))
    cell = registry.workload("flat-serve.burst")
    assert registry.traffic(cell["traffic"])["kind"] == "serve_open_loop"
    names = [m["name"] for m in
             registry.cell_metrics(bench, "flat-serve.burst", True)]
    # no workloads key: it joins every cell that reports serve_p95_ms
    assert "queue.depth.serve" in names
    assert registry.metric_reader("queue.depth.serve").read(None) == 3.0
    assert "queue.depth.serve" in [
        m["name"] for m in registry.cell_metrics(bench, "flat-serve.poisson",
                                                 True)]
    assert "queue.depth.serve" not in [
        m["name"] for m in registry.cell_metrics(bench, "msmarco-encode.bulk",
                                                 True)]
