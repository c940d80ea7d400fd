"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--only table1,table3] [--quick]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.launch.jaxcache import use_compile_cache

from .common import Row

ALL = ("rabitq_error", "allocate_bench", "table1_quality",
       "table2_calibration", "table3_time", "serve_bench",
       "roofline_report")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark module names")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(ALL)
    use_compile_cache()
    row = Row()
    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for name in names:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t1 = time.time()
        try:
            mod.run(row)
        except Exception as e:  # run the rest, then fail the whole run
            traceback.print_exc()
            row.add(f"{name}/ERROR", 0.0, f"{type(e).__name__}:{e}")
            failed.append(name)
        print(f"# {name} done in {time.time()-t1:.1f}s", file=sys.stderr,
              flush=True)
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
