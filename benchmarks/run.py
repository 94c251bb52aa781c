"""Benchmark harness entry point — one module per paper table/figure.

  bench_mining    Fig. 8/9/13 (mining speedups vs CPU + exhaustive check)
  bench_kernels   Fig. 11/12  (IU-count / S-Cache-bandwidth analogues)
  bench_streams   Fig. 14     (stream length distributions)
  bench_sparse    Fig. 15     (SpMM / TTV via S_VINTER)
  bench_roofline  EXPERIMENTS.md §Roofline table from dry-run artifacts

Usage: PYTHONPATH=src python -m benchmarks.run [--full]

A failing suite is recorded in the results file and the rest still run;
the exit code is then 1.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import (bench_kernels, bench_mining, bench_roofline,
                        bench_sparse, bench_streams)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full dataset sweep (slow); default quick mode")
    ap.add_argument("--only", default="",
                    help="comma list: mining,kernels,streams,sparse,roofline")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "experiments", "bench_results.json"),
        help="results JSON path")
    args = ap.parse_args(argv)
    quick = not args.full
    wanted = set(args.only.split(",")) if args.only else None
    suites = {
        "mining": bench_mining.run,
        "kernels": bench_kernels.run,
        "streams": bench_streams.run,
        "sparse": bench_sparse.run,
        "roofline": bench_roofline.run,
    }
    results, failed = {}, []
    for name, fn in suites.items():
        if wanted and name not in wanted:
            continue
        print(f"\n===== bench: {name} =====", flush=True)
        t0 = time.time()
        try:
            results[name] = fn(quick=quick)
        except Exception as e:  # keep the harness going; record the failure
            print(f"[{name}] FAILED: {e!r}", flush=True)
            results[name] = {"error": repr(e)}
            failed.append(name)
        print(f"===== {name} done in {time.time()-t0:.1f}s =====", flush=True)
    out = args.out
    os.makedirs(os.path.dirname(out), exist_ok=True)

    def default(o):
        return str(o)

    json.dump(results, open(out, "w"), indent=1, default=default)
    print(f"\n[bench] results -> {out}")
    if failed:
        print(f"[bench] FAILED suites: {', '.join(failed)}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
