#!/usr/bin/env python3
"""Record the small TPU trace the tests of the host-phase readers read
(`benchmarks/tests/data/small_phases.xplane.pb`), and answer on the way
which clock a profiler trace stamps its host plane with.

    python3 benchmarks/tools/record_phase_trace.py OUT.xplane.pb

Six iterations of a loop shaped like the decode loop: a
`paddle.genserve/decode` annotation around the dispatch of a small jitted
program, a `paddle.genserve/fetch` annotation around the fetch of its
result, a `paddle.genserve/distribute` one around a millisecond of host
work, all inside `bench.window`.  `time.time_ns()` and
`time.monotonic_ns()` are read right before and after the window opens;
the last line printed says between which pair the window's start lies
once the trace's `profile_start_time` (a stat of its `Task Environment`
plane, nanoseconds) is added to the event's `start_ns`.
"""
import glob
import json
import os
import shutil
import sys
import tempfile
import time


def main(out):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData, TraceAnnotation

    if jax.devices()[0].platform != "tpu":
        sys.exit("needs a TPU")

    @jax.jit
    def decode_step(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x.astype(jnp.float32).sum()

    x = jnp.full((2048, 2048), 0.01, jnp.bfloat16)
    decode_step(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    time.sleep(0.01)
    before = {"time_ns": time.time_ns(), "monotonic_ns": time.monotonic_ns()}
    with TraceAnnotation("bench.window"):
        after = {"time_ns": time.time_ns(),
                 "monotonic_ns": time.monotonic_ns()}
        for _ in range(6):
            with TraceAnnotation("paddle.genserve/decode"):
                y = decode_step(x)
            with TraceAnnotation("paddle.genserve/fetch"):
                np.asarray(y)
            with TraceAnnotation("paddle.genserve/distribute"):
                time.sleep(0.001)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, out)
    shutil.rmtree(d)
    start = zero = None
    for plane in ProfileData.from_file(out).planes:
        for k, v in plane.stats:
            if k == "profile_start_time":
                zero = int(v)
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench.window":
                    start = int(e.start_ns)
    stamp = None if None in (start, zero) else zero + start
    print(json.dumps({
        "out": out, "bytes": os.path.getsize(out),
        "window_start_ns": start, "profile_start_time": zero,
        "window_start_on_trace_clock": stamp, "before": before,
        "after": after,
        "host_plane_clock": next(
            (k for k in before
             if stamp is not None and before[k] <= stamp <= after[k]),
            "neither")}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
