"""Benchmark harness: one module per paper table/figure and the analytic
TPU design-space exploration. Prints ``name,us_per_call,derived`` CSV lines.

Usage:
    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python -m benchmarks.run --only fig6 # substring filter
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def _modules():
    # imported lazily so a failure in one bench doesn't kill the others
    names = [
        "benchmarks.paper_repro",
        "benchmarks.tpu_dse",
    ]
    for name in names:
        try:
            __import__(name)
            yield name, sys.modules[name]
        except Exception:
            print(f"{name},ERROR,import_failed")
            traceback.print_exc()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter on row names")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    for name, mod in _modules():
        t0 = time.perf_counter()
        try:
            rows = mod.run()
        except Exception:
            print(f"{name},ERROR,run_failed")
            traceback.print_exc()
            continue
        for row in rows:
            if args.only and args.only not in row:
                continue
            print(row)
        dt = time.perf_counter() - t0
        print(f"{name}.total,{dt*1e6:.0f},ok")


if __name__ == "__main__":
    main()
