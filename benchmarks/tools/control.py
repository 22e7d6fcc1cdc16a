#!/usr/bin/env python3
"""The control of a cell's `correct`: the run with the plain reference, in
the precision below the one the configuration states (the traffic file's
`control_precision`), put in the program's place.  Its result line has to
say `"correct": false`; the benchmark's own runs never run it.

    python3 benchmarks/tools/control.py --workload NAME --seed N --seconds S

fit: the reference's first steps in that precision against the float32
reference (no window).  Serving: a short window of the real program, then
at each position of the sampled prompts and served tokens the gap of the
token that the lower precision puts first.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402
from benchmarks.run import run_cell  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    manifest = common.load_manifest()
    _, _, _, traffic = common.resolve_cell(manifest, args.workload)
    run_cell(args.workload, args.seed, args.seconds, 0,
             control=traffic["control_precision"])
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
