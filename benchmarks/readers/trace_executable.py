"""The median device time (ms) of one run of the executables whose name
on the trace's `XLA Modules` line matches `pattern`."""
from benchmarks import stats, trace


def read(run, pattern):
    if run.trace_data is None:
        return None
    xs = [d / 1e6 for _, d in
          run.trace_data.events(trace.MODULES_LINE, pattern)]
    return stats.median(xs) if xs else None
