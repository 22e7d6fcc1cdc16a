"""A `StepTimers` phase's total over the window as a share of the
window, in percent."""


def read(run, phase):
    if phase not in run.steptimers or run.window is None:
        return None
    t0, t1 = run.window
    return 100.0 * run.steptimers[phase] / (t1 - t0)
