"""The device's idle share of the traced window, in percent: 1 - the union
of the device operations' intervals over the window, averaged over the
chips used."""


def read(run):
    td = run.trace_data
    if td is None or td.window_s <= 0 or td.busy_s <= 0:
        return None
    return 100.0 * (1.0 - td.busy_s / td.window_s)
