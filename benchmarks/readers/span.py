"""A percentile of the durations (ms) of the program's spans of one name
that began in the window."""
from benchmarks import stats


def read(run, span, q):
    xs = [s["dur_ms"] for s in run.spans if s["name"] == span]
    return stats.percentile(xs, q) if xs else None
