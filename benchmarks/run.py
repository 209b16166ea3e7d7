"""Benchmark harness — one entry per paper table (+ kernel benches).

Prints ``name,us_per_call,derived`` CSV rows (see DESIGN.md §7 index):
  Table 1  memory: naive vs Trove data management, plus the ConcatView
           combined-corpus streaming variant (+ results/*.json)
  Table 2  multi-node inference scaling (simulated nodes)
  Table 3  Python heapq vs FastResultHeapq (online / cached)
  Table 4  time-to-first-sample, first vs warm run
  kernels  fused score+top-k HBM-traffic reduction
  search   score_impl backends: host-numpy baseline vs device paths
  multinode  ShardedSearchDriver scaling W=1,2,4 (+ results/*.json)
  dispatch  per-chunk streaming vs superchunk scan (+ results/*.json)
  encode   legacy per-batch padding vs bucketed pipeline (+ results/*.json)
  serve    sequential per-request loop vs continuous-batching frontend
           QPS/p50/p99 curve over submitter concurrency (+ results/*.json)
  ivf      flat exhaustive scan vs IVF cluster-pruned search: recall@10
           vs speedup over the nprobe sweep (+ results/*.json)
  mutation serve QPS/p99 under sustained live corpus mutation vs a
           frozen corpus, compaction pause, post-compaction scan
           speedup (+ results/*.json)

``run.py --check [--tol T]`` re-runs the JSON-emitting benches into a
scratch dir and compares their key metrics against the committed
baselines in ``results/`` — exits nonzero on regression.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    print("name,us_per_call,derived")
    from benchmarks import (bench_dispatch, bench_encode, bench_faults,
                            bench_ivf, bench_kernels, bench_memory,
                            bench_multinode, bench_mutation,
                            bench_result_heap, bench_scaling,
                            bench_search_backends, bench_serve,
                            bench_ttfs)
    bench_result_heap.run()
    bench_scaling.run()
    bench_ttfs.run()
    bench_memory.run()
    bench_kernels.run()
    bench_search_backends.run()
    bench_multinode.run()
    bench_dispatch.run()
    bench_encode.run()
    bench_serve.run()
    bench_ivf.run()
    bench_faults.run()
    bench_mutation.run()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks.check import main as check_main
    if "--check" in sys.argv[1:]:
        sys.exit(check_main(sys.argv[1:]))
    main()
