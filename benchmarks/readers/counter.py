"""A share of program counters over the window: 100 * sum(numerator) /
sum(denominator).  None where the denominator counted nothing."""


def read(run, numerator, denominator):
    den = sum(run.counters.get(k, 0) for k in denominator)
    if not run.counters or den == 0:
        return None
    return 100.0 * sum(run.counters.get(k, 0) for k in numerator) / den
