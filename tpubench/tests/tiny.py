"""Tiny stand-ins for the configuration files, so a whole run fits on
the CPU in seconds.  Same keys as the real files; widths and sizes cut."""

import copy

from tpubench import registry


def config(name: str = "trove-base.msmarco-1m.flat", **evaluation) -> dict:
    c = copy.deepcopy(registry.config(name))
    c.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             intermediate_size=128, vocab_size=1024, num_passages=4096)
    c["evaluation"].update(serve_max_batch=8, encode_batch_size=64)
    if c["evaluation"].get("index_impl") == "ivf":
        c["evaluation"].update(ivf_nclusters=16, ivf_nprobe=4,
                               ivf_train_batch=256, ivf_train_steps=4)
    c["evaluation"].update(evaluation)
    c["corpus_vectors"].update(anchors=64, anchor_tokens=16)
    c["text"]["words"]["vocab"] = 500
    return c


def workload(cell: str, **extra) -> dict:
    w = copy.deepcopy(registry.workload(cell))
    if "rate_qps" in w:
        w["rate_qps"] = 20.0
    w.update(extra)
    return w


def facts() -> dict:
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
