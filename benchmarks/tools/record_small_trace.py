#!/usr/bin/env python3
"""Record the small TPU trace the tests of the reduction read
(`benchmarks/tests/data/small_tpu.xplane.pb`): three bursts of a small
jitted program with sleeps between, inside a `bench.window` annotation.

    python3 benchmarks/tools/record_small_trace.py OUT.xplane.pb
"""
import glob
import os
import shutil
import sys
import tempfile
import time


def main(out):
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        sys.exit("needs a TPU")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("paddle.fit/dispatch"):
                for _ in range(4):
                    y = f(x)
            y.block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, out)
    shutil.rmtree(d)
    print(out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
