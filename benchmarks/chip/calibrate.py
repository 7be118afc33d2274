"""Readings that set the benchmark's fixed numbers, made on the chip in one
process (so the set-up is paid once):

    python3 benchmarks/chip/calibrate.py readings --workload <cell> --seeds 1,2,3 \\
        --seconds 51 [--controls int8,fp8]
    python3 benchmarks/chip/calibrate.py sweep --workload <cell> --seeds 7,8,9 \\
        --seconds 51 --rates 0.2,0.3,0.4

``readings`` runs the cell once per seed and prints, per seed, the numbers
compared and, with ``--controls``, the control's (the reference computed in
that lower precision, read at the same positions): the lower and upper
readings that each limit is set between. ``sweep`` runs an open-loop cell's
lead-in and window at each offered rate on each seed, without the drain and
the check, and prints what was offered beside what was delivered: the knee,
the highest rate the system sustains, is where delivered output stops
tracking offered output.
One JSON object per line on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import run as bench  # noqa: E402


def _cell(manifest, workload, seed, seconds, trace):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds, trace=trace)
    entry = next(w for w in manifest["workloads"] if w["name"] == workload)
    device = bench.device_or_exit(entry["chips"])
    return bench.build_cell(manifest, args, device)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--controls", default="")
    args = ap.parse_args(argv)

    manifest = bench.load_json(bench.ROOT / "BENCHMARK.json")
    bench.configure_cache()
    if args.mode == "readings":
        controls = tuple(c for c in args.controls.split(",") if c)
        for seed in (int(s) for s in args.seeds.split(",")):
            cell, driver = _cell(manifest, args.workload, seed, args.seconds, 0)
            result, _ = bench.execute(cell, driver, manifest, controls=controls)
            print(json.dumps({"seed": seed, **result}), flush=True)
    else:
        for rate in (float(r) for r in args.rates.split(",")):
            for seed in (int(s) for s in args.seeds.split(",")):
                cell, driver = _cell(manifest, args.workload, seed, args.seconds, 0)
                cell.traffic = dict(cell.traffic, rate_per_s=rate)
                state = driver.setup(cell)
                rec = driver.window(cell, state, bench.Clock(cell.seconds, None), drain_s=0.0)
                driver.release(state)
                print(json.dumps({"rate_per_s": rate, "seed": seed,
                                  "metrics": driver.end_to_end(cell, rec),
                                  "summary": driver.summary(rec)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
