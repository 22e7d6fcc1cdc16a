"""A percentile of what the load generator stamped at the client:
`ttft` (due instant to first token, requests due in the window; a request
with no token counts as the rest of the run), `itl` (gaps between a
request's consecutive tokens that end in the window), `late` (how late
the generator sent against its schedule).  Milliseconds."""
from benchmarks import stats


def samples(run, what):
    if run.client is None:
        return None
    t0, t1 = run.window
    recs = [r for r in run.client["records"] if t0 <= r["due"] < t1]
    if what == "ttft":
        worst = run.client["collected_until"]
        return [((r["t"][0] if r["t"] else worst) - r["due"]) * 1e3
                for r in recs]
    if what == "late":
        return [(r["sent"] - r["due"]) * 1e3 for r in recs
                if r["sent"] is not None]
    if what == "itl":
        return [g * 1e3 for r in run.client["records"]
                for g in stats.gaps_ending_in(r["t"], t0, t1)]
    raise ValueError(f"unknown client sample {what!r}")


def read(run, what, q):
    xs = samples(run, what)
    return stats.percentile(xs, q) if xs else None
