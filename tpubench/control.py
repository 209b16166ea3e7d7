#!/usr/bin/env python3
"""Readings that set the upper end of each compared number's limit.

For each seed, at the cell's own size, the inputs of a run are made
again (weights, corpus vectors, the window's queries or the checked
sample of passages), and the cell's numbers are read for:

* ``control`` -- the reference one precision step below the
  configuration (float8 encoder matmuls, float8 scan) put in the
  program's place;
* ``altered`` -- the exact reference answer with one answer altered
  where it is produced (the top id of every query replaced by a
  seeded random row, its score kept; for passages, one vector per
  passage replaced by another passage's).

    python3 tpubench/control.py --workload flat-serve.poisson --seeds 1,2,3 --seconds 40

The benchmark's runs never run this.  ``PERF.md`` records its readings
beside the program's, and each limit in ``workloads/<cell>.json`` lies
between them.
"""

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpubench import harness  # noqa: E402


def serve_readings(run, seed: int, seconds: float) -> dict:
    from tpubench import compare, reference, textgen
    from tpubench.system import System

    cfg, k = run.config, run.config["evaluation"]["topk"]
    sysm = System(cfg, seed)
    corpus = sysm.corpus_vectors(sysm.anchors())
    n = len(textgen.poisson_schedule(run.workload["rate_qps"], seconds, seed))
    texts = textgen.make_texts(n, run.mix["query_words"], cfg["text"]["words"],
                               seed, "query")
    out = {"control": compare.control(sysm, texts, corpus, k)}
    q = reference.encode_texts(sysm.params, texts, sysm.enc,
                               cfg["query_max_len"])
    top_v, top_i = reference.exact_topk(q, corpus, k)
    rng = textgen.rng_for(seed, "fault")
    ids = top_i.copy()
    ids[:, 0] = rng.integers(0, corpus.shape[0], len(ids))
    out["altered"] = dict(compare.numbers(q, corpus, ids, top_v, top_v,
                                          top_i), unanswered=0.0)
    out["exact"] = dict(compare.numbers(q, corpus, top_i, top_v, top_v,
                                        top_i), unanswered=0.0)
    del corpus
    return {name: {k2: float(v) for k2, v in nums.items()}
            for name, nums in out.items()} | {"queries": len(texts),
                                              "seed": seed}


def encode_readings(run, seed: int) -> dict:
    import numpy as np

    from tpubench import compare, reference, textgen
    from tpubench.system import System

    cfg, mix = run.config, run.mix
    sysm = System(cfg, seed)
    texts = textgen.make_texts(mix["check_sample"], mix["passage_words"],
                               cfg["text"]["words"], seed, "passage0")
    dtype = np.dtype(cfg["storage_dtype"])
    v8 = reference.encode_texts(sysm.params, texts, sysm.enc,
                                cfg["passage_max_len"], precision="float8")
    out = {"control": compare.encoded(sysm, texts,
                                      list(v8.astype(dtype)))}
    v = reference.encode_texts(sysm.params, texts, sysm.enc,
                               cfg["passage_max_len"])
    shifted = np.roll(v, 1, axis=0).astype(dtype)
    out["altered"] = compare.encoded(sysm, texts, list(shifted))
    out["stored"] = compare.encoded(sysm, texts, list(v.astype(dtype)))
    return {name: {k2: float(x) for k2, x in nums.items()}
            for name, nums in out.items()} | {"seed": seed}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args()
    try:
        bench, facts = harness.open_cell(args.workload)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return harness.NO_CHIP
    harness.use_compile_cache()
    run = harness.Run(args.workload, 0, args.seconds, False, T_START,
                      bench=bench)
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            if run.mix["kind"] == "bulk_encode":
                reading = encode_readings(run, seed)
            else:
                reading = serve_readings(run, seed, args.seconds)
            print(json.dumps({"cell": args.workload, "device": facts,
                              **reading}), flush=True)
    finally:
        run.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
