#!/usr/bin/env python3
"""The rate sweep that finds an open-loop cell's knee: one process, one
warm server, each rate offered for `--seconds` after the cell's ramp.

    python3 benchmarks/tools/sweep.py --workload NAME --seed N \\
        --rates 2,3,4,5,6,7 --seconds 25

One JSON line a rate: requests due, refused or failed, the share of the
output tokens offered in the window that the clients received in it, and
the tails.  The knee is the highest rate whose share is at least 97% with
nothing refused; the cell's rate, written into its traffic file as a
number, is four fifths of it.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import common, stats, traffic as gen  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    args = ap.parse_args(argv)
    manifest = common.load_manifest()
    cell, entry, config, spec = common.resolve_cell(manifest, args.workload)
    common.use_compile_cache()
    import jax

    common.require_devices(jax, cell["chips"])
    import paddle_tpu  # noqa: F401

    common.cache_everything(jax)
    from benchmarks.kinds import serve
    from benchmarks.readers import client_percentile

    run = bench_run.Run(manifest, cell, entry, config, spec, args.seed,
                        args.seconds, 0)
    run.meter = common.CompileMeter(jax)
    run.phases = common.Phases(run.meter)
    run.control = None
    served = serve.Served(run)
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            s = dict(spec, rate_per_s=rate)
            ramp = spec["ramp_s"]
            reqs = gen.serve_schedule(s, args.seed + i,
                                      ramp + args.seconds,
                                      config["model"]["vocab_size"])
            w = served.window(reqs, args.seconds, ramp, tag=f"rate{i}")
            t0, t1 = run.window = w["t0"], w["t1"]
            run.client = w["client"]
            recs = w["client"]["records"]
            by_id = {r["id"]: r for r in reqs}
            due = [r for r in recs if t0 <= r["due"] < t1]
            offered = sum(by_id[r["id"]]["max_new"] for r in due)
            got = sum(1 for r in recs for t in r["t"] if t0 <= t < t1)
            ttft = client_percentile.samples(run, "ttft")
            itl = client_percentile.samples(run, "itl")
            print(json.dumps({
                "rate_per_s": rate, "requests_due": len(due),
                "refused_or_failed": sum(1 for r in due if r["error"]
                                         or r["status"] != 200),
                "tokens_offered": offered, "tokens_received": got,
                "received_share": got / offered if offered else None,
                "ttft_p50_ms": stats.percentile(ttft, 50),
                "ttft_p95_ms": stats.percentile(ttft, 95),
                "itl_p50_ms": stats.percentile(itl, 50),
                "itl_p95_ms": stats.percentile(itl, 95),
                "occupancy_mean": sum(w["occupancy"]) / len(w["occupancy"]),
                "device": jax.devices()[0].device_kind}), flush=True)
    finally:
        served.close()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
