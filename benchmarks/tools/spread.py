#!/usr/bin/env python3
"""Spreads and bounds from the logs of two sets of runs of one cell.

    python3 benchmarks/tools/spread.py SET1_DIR_OR_GLOB SET2_DIR_OR_GLOB

Each argument is a glob of log files, one run a file, whose last line is a
result line.  For each metric: each set's median and spread (the distance
between the first and third quartile by `statistics.quantiles(n=4)` as a
share of the median), the wider of the two, five times it, how far the
second set's median lies from the first's, and the reading the driver
takes for tightness: the mean of the two spreads with each set's run
farthest from its median left out.  `setup_s` leaves out each set's first
run, which compiles.
"""
import glob
import json
import statistics
import sys


def results(pattern):
    out = []
    for path in sorted(glob.glob(pattern)):
        lines = [ln for ln in open(path).read().splitlines() if ln.strip()]
        try:
            out.append(json.loads(lines[-1]))
        except (IndexError, ValueError):
            print(f"no result line in {path}", file=sys.stderr)
    return out


def spread(xs):
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)


def without_farthest(xs):
    med = statistics.median(xs)
    far = max(xs, key=lambda x: abs(x - med))
    return [x for i, x in enumerate(xs) if i != xs.index(far)]


def main(a, b):
    sets = [results(a), results(b)]
    for i, s in enumerate(sets):
        print(f"set {i + 1}: {len(s)} runs, correct "
              f"{sum(r['correct'] for r in s)}/{len(s)}, failed "
              f"{sum(r['failed'] for r in s)}")
    names = sorted({n for s in sets for r in s for n in r["metrics"]})
    for n in names:
        row, spreads, meds, tight = [n], [], [], []
        for s in sets:
            xs = [r["metrics"][n]["value"] for r in s if n in r["metrics"]]
            if n == "setup_s":
                xs = xs[1:]
            med = statistics.median(xs)
            spreads.append(spread(xs))
            meds.append(med)
            if len(xs) >= 3:
                tight.append(spread(without_farthest(xs)))
            row.append(f"median {med:.6g} spread {spreads[-1]:.4%} "
                       f"[{min(xs):.6g} .. {max(xs):.6g}]")
        row.append(f"wider {max(spreads):.4%} x5 {5 * max(spreads):.4%} "
                   f"set2/set1 {meds[1] / meds[0] - 1:+.4%} "
                   f"tightness {statistics.mean(tight):.4%}"
                   if tight else "")
        print(" | ".join(row))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
