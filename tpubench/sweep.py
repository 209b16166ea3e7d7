#!/usr/bin/env python3
"""Find the rate a serve cell sustains: one set-up, then one open-loop
window per offered rate, each printed as a line (offered and completed
rate, p50 and p95, how late the generator ran).  The cell's workload
file records the rate chosen from this, as a number; the benchmark runs
never search.

    python3 tpubench/sweep.py --workload flat-serve.poisson --seed 1 \\
        --seconds 40 --rates 3,4.5,6,7.5
"""

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpubench import harness, registry  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    try:
        bench, facts = harness.open_cell(args.workload)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return harness.NO_CHIP
    harness.use_compile_cache()
    run = harness.Run(args.workload, args.seed, args.seconds, False, T_START,
                      bench=bench)
    try:
        run.listen_compiles()
        driver = registry.traffic_driver(run.mix["kind"])
        state = driver.setup(run)
        print(json.dumps({"sweep": args.workload, "device": facts,
                          "setup_s": time.monotonic() - T_START}), flush=True)
        last = None
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            w = driver.window(run, state, rate, args.seconds, args.seed + i)
            t_window = args.seconds
            print(json.dumps({
                "offered_qps": rate, "sent": w["sent"],
                "completed": w["completed"],
                "completed_qps": w["serve_qps"],
                "p50_ms": w["serve_p50_ms"], "p95_ms": w["serve_p95_ms"],
                "generator_late_p50_ms": w["late_p50_ms"],
                "generator_late_max_ms": w["late_max_ms"],
                "window_s": t_window,
                "compiles_in_window": len(w["compiles_in_window"]),
                "mean_round_s": (sum(t1 - t0 for t0, t1, _ in w["batch_walls"])
                                 / max(1, len(w["batch_walls"]))),
                "mean_batch": (w["frontend"][1]["queries"]
                               - w["frontend"][0]["queries"])
                / max(1, w["frontend"][1]["batches"]
                      - w["frontend"][0]["batches"])}), flush=True)
            last = w
        numbers = driver.check(run, state, last["texts"], last["served"])
        print(json.dumps({"last_window_numbers": numbers}), flush=True)
    finally:
        run.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
