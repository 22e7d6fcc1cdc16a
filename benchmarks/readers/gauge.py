"""The mean of a gauge's samples, taken once a second in the serving
process itself, times `scale`."""


def read(run, gauge, scale=1.0):
    xs = run.gauges.get(gauge)
    return scale * sum(xs) / len(xs) if xs else None
