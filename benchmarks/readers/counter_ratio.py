"""A ratio of program counters over the window: scale * sum(numerator) /
sum(denominator), as it is (the `counter` reader gives a percentage).
None where the program has no such counters or the denominator counted
nothing."""


def read(run, numerator, denominator, scale=1.0):
    if not all(k in run.counters for k in numerator + denominator):
        return None
    den = sum(run.counters[k] for k in denominator)
    if den == 0:
        return None
    return scale * sum(run.counters[k] for k in numerator) / den
