#!/usr/bin/env python3
"""The sets of runs a cell's bounds are set from: `--sets` sets over the
same `--seeds`, one process a run as the driver makes them, each run's
output in `<out>/<cell>.set<k>.<seed>.log`, then the spreads
(`tools/spread.py`).  Imports no jax: each run has the chip to itself.

    python3 benchmarks/tools/sets.py --workload NAME --seeds 1,2,3,4,5,6 \\
        [--sets 2] [--seconds 40] [--trace 0] [--out chiprun_out/sets] \\
        [--until EPOCH_SECONDS] [--manifest FILE] [--keep-records]

It stops at the first run that gives no result line (nothing after it
would be worth its chip time), and starts no run after `--until`.
"""
import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")
sys.path.insert(0, HERE)

import spread  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/sets")
    ap.add_argument("--until", type=float, default=None)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--keep-records", action="store_true")
    args = ap.parse_args(argv)
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                               "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    base = os.path.join(args.out, args.workload)
    for k in range(1, args.sets + 1):
        for seed in args.seeds.split(","):
            if args.until is not None and time.time() > args.until:
                print(f"out of time before set {k} seed {seed}", flush=True)
                return 2
            cmd = [sys.executable, RUN, "--workload", args.workload,
                   "--seed", seed, "--seconds", str(seconds), "--trace",
                   str(args.trace)]
            if args.manifest:
                cmd += ["--manifest", args.manifest]
            if args.keep_records:
                cmd += ["--keep-records",
                        os.path.join(args.out, f"records.set{k}")]
            log = f"{base}.set{k}.{seed}.log"
            t = time.time()
            with open(log, "w") as out, \
                    open(log[:-4] + ".err", "w") as err:
                rc = subprocess.call(cmd, stdout=out, stderr=err)
            lines = open(log).read().splitlines()
            last = lines[-1] if lines else ""
            print(f"set {k} seed {seed}: exit {rc} in "
                  f"{time.time() - t:.0f} s: {last[:400]}", flush=True)
            if rc != 0 or '"correct"' not in last:
                print(open(log[:-4] + ".err").read()[-3000:], flush=True)
                return 1
    if args.sets >= 2:
        spread.main(f"{base}.set1.*.log", f"{base}.set2.*.log")
    return 0


if __name__ == "__main__":
    sys.exit(main())
