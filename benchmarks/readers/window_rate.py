"""A count of work done in the window over the window's seconds."""


def read(run, count):
    if count not in run.counts or run.window is None:
        return None
    t0, t1 = run.window
    return run.counts[count] / (t1 - t0)
