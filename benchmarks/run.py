#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration and a traffic mix; their files under
`benchmarks/` say everything else (the traffic file's `kind` picks the
module under `benchmarks/kinds/` that drives the program).  The last line
of standard output is the result, as the contract has it; a run that
cannot give one exits non-zero and prints none.  Without a TPU (or with
fewer chips than the cell asks for) it fails: nothing falls back.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import common  # noqa: E402  (stamps the process start)


class Run:
    """What a kind's driver gets, and what the metric readers read."""

    def __init__(self, manifest, cell, entry, config, traffic, seed, seconds,
                 trace):
        self.manifest, self.cell, self.entry = manifest, cell, entry
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.checks = common.Checks()
        self.attempted = self.failed = 0
        self.setup_s = None
        self.window = None          # (t0, t1), time.monotonic()
        self.counts = {}            # work counted in the window, by name
        self.client = None          # the load generator's records
        self.counters = {}          # program counters, deltas over window
        self.gauges = {}            # name -> samples taken in the window
        self.spans = []             # the program's finished spans
        self.steptimers = {}        # StepTimers totals over the window
        self.trace_data = None      # benchmarks.trace.TraceData
        self.peaks = None
        self.breakdown = None
        self.keep_trace = self.keep_records = None   # --keep-* directories


def read_metrics(run, names):
    """Each metric by its own file: `end_to_end/<name>.json` or
    `layer_metrics/<name>.json` names a reader under `readers/` and its
    arguments.  A reader that finds nothing returns None and the metric
    is left out of the line."""
    units = {m["name"]: m["unit"] for m in
             run.manifest["end_to_end"] + run.manifest["per_layer"]}
    e2e = {m["name"] for m in run.manifest["end_to_end"]}
    out = {}
    for name in names:
        spec = common.named_file(
            "end_to_end" if name in e2e else "layer_metrics", name)
        value = common.plugin("readers", spec["reader"]).read(
            run, **spec.get("args", {}))
        if value is None:
            continue
        out[name] = {"value": float(value), "unit": units[name]}
    return out


def run_cell(workload, seed, seconds, trace, manifest_path=None,
             platform="tpu", keep_trace=None, keep_records=None,
             control=None, prepare=None):
    """Drive one cell and print its result line.  `platform=None` skips
    the look for a chip (tests and the CPU rehearsal only; a rehearsal's
    line says the platform it ran on and is never a result)."""
    manifest = common.load_manifest(manifest_path)
    cell, entry, config, traffic = common.resolve_cell(manifest, workload)
    common.use_compile_cache()
    import jax

    if platform is not None:
        common.require_devices(jax, cell["chips"], platform)
    import paddle_tpu  # noqa: F401  (sets its cache options; ours go last)

    common.cache_everything(jax)
    run = Run(manifest, cell, entry, config, traffic, seed, seconds, trace)
    run.meter = common.CompileMeter(jax)
    run.phases = common.Phases(run.meter)
    run.phases.mark("imports")
    run.keep_trace, run.keep_records = keep_trace, keep_records
    run.on_chip = platform is not None
    run.control = control       # tools/control.py and the tests only
    if prepare:                 # tests: break the timed path underneath
        prepare(run)
    if platform is not None:
        run.peaks = common.peaks_for(jax.devices()[0].device_kind)
    common.plugin("kinds", traffic["kind"]).drive(run)
    names = [] if control else common.metrics_for(manifest, cell, traffic,
                                                  trace)
    metrics = read_metrics(run, names)
    device = run.device
    if run.trace and run.trace_data is not None:
        device["busy_s"] = run.trace_data.busy_s
        device["window_s"] = run.trace_data.window_s
        run.breakdown = run.trace_data.breakdown()
    common.result_line(run.checks.ok, run.attempted, run.failed, metrics,
                       device, run.breakdown)
    return run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: also copy the .xplane.pb there")
    ap.add_argument("--keep-records", default=None, metavar="DIR",
                    help="serving: also write the client's time stamps there")
    ap.add_argument("--manifest", default=None, metavar="FILE",
                    help="another manifest than BENCHMARK.json: trials of a "
                    "cell before it is entered there")
    args = ap.parse_args(argv)
    try:
        run_cell(args.workload, args.seed, args.seconds, args.trace,
                 args.manifest, keep_trace=args.keep_trace,
                 keep_records=args.keep_records)
    except common.BenchFailure as e:
        sys.stderr.write(f"benchmarks/run.py: {e}\n")
        return 3
    except Exception:  # noqa: BLE001 - boundary: report, exit non-zero
        traceback.print_exc()
        return 1
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (HTTP, prefetch) must not hold the exit
    os._exit(code)
