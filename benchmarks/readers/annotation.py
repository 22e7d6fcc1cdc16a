"""A percentile (ms) over the program's host annotations of exactly one
name in the traced window (`paddle.genserve/admit` does not match
`paddle.genserve/admit/fetch`): of their durations, or with `period` of
the intervals from one's start to the next one's.  A period in which an
annotation named `skip_if_holds` began is left out (the decode loop's
`wait`: then no lane was waiting for a token).  None without a device
plane (off the chip) or where the program has no such annotation."""
import bisect

from benchmarks import stats


def read(run, name, q, period=False, skip_if_holds=None):
    td = run.trace_data
    if td is None or not any(td.devices.values()):
        return None
    lo, hi = td.window
    spans = sorted((a, b) for n, a, b in td.host
                   if n == name and a >= lo and b <= hi)
    if not period:
        xs = [(b - a) / 1e6 for a, b in spans]
    else:
        skip = sorted(a for n, a, _ in td.host if n == skip_if_holds)
        xs = [(t1 - t0) / 1e6
              for (t0, _), (t1, _) in zip(spans, spans[1:])
              if bisect.bisect_left(skip, t0) == bisect.bisect_left(skip, t1)]
    return stats.percentile(xs, q) if xs else None
