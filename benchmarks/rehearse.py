#!/usr/bin/env python3
"""CPU rehearsal of every cell of BENCHMARK.json at a tiny size.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py [--workload NAME]
        [--seconds 3] [--trace 0|1] [--seed N]

Each cell keeps its kind of traffic and its metrics but runs the
configuration `tiny-gpt` under the traffic file `tiny-<traffic>` (both are
data under `benchmarks/`, named by no cell); a `tiny-<mix>` that no cell
runs yet is rehearsed as `tiny-gpt.<mix>`.  It finds wrong paths,
arguments and control flow at no chip time.  Its lines say the platform
they ran on; they are never results, and no number it prints is a device
metric.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def tiny_manifest(manifest):
    """Every cell under `tiny-gpt` and `tiny-<traffic>`; a mix that no
    cell runs yet is rehearsed too, as `tiny-gpt.<mix>`, so that every
    mode of the generator stays driven."""
    out = dict(manifest)
    out["configs"] = [{"name": "tiny-gpt", "source": "none",
                       "file": "benchmarks/configs/tiny-gpt.json",
                       "reduced": [], "why": "rehearsal"}]
    out["workloads"] = [dict(w, config="tiny-gpt",
                             traffic="tiny-" + w["traffic"])
                        for w in manifest["workloads"]]
    used = {w["traffic"] for w in out["workloads"]}
    for f in sorted(os.listdir(os.path.join(ROOT, "benchmarks", "traffic"))):
        mix = f[:-len(".json")]
        if mix.startswith("tiny-") and mix not in used:
            out["workloads"].append({
                "name": "tiny-gpt." + mix[len("tiny-"):], "chips": 1,
                "config": "tiny-gpt", "traffic": mix, "why": "rehearsal"})
    return out


def one(workload, seed, seconds, trace, manifest_path):
    os.environ.setdefault("PADDLE_TPU_ENABLE_X64", "0")  # the chip's regime
    from benchmarks.run import run_cell

    run_cell(workload, seed, seconds, trace, manifest_path, platform=None)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=None)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 11)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--manifest", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.manifest:                       # the child of one cell
        one(args.workload, args.seed, args.seconds, args.trace,
            args.manifest)
        return 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = tiny_manifest(json.load(f))
    names = [w["name"] for w in manifest["workloads"]
             if args.workload in (None, w["name"])]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    failed = []
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "rehearsal.json")
        with open(path, "w") as f:
            json.dump(manifest, f)
        for name in names:          # one process a cell, as on the chip
            rc = subprocess.call(
                [sys.executable, os.path.abspath(__file__), "--workload",
                 name, "--seed", str(args.seed), "--seconds",
                 str(args.seconds), "--trace", str(args.trace),
                 "--manifest", path], env=env, cwd=ROOT)
            print(json.dumps({"rehearsal": True, "workload": name,
                              "platform": "cpu", "exit": rc}), flush=True)
            if rc:
                failed.append(name)
    return 1 if failed else 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
