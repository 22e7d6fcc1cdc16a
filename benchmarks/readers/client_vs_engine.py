"""The serving front's share of the time to first token: the median, over
the window's requests, of the client's TTFT (sent instant to first token)
less the `ttft_ms` the engine reports in the same reply."""
from benchmarks import stats


def read(run):
    if run.client is None:
        return None
    t0, t1 = run.window
    xs = [(r["t"][0] - r["sent"]) * 1e3 - r["done"]["ttft_ms"]
          for r in run.client["records"]
          if t0 <= r["due"] < t1 and r["t"] and r["done"]
          and r["done"].get("ttft_ms") is not None]
    return stats.median(xs) if xs else None
