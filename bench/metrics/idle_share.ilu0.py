"""idle_share.ilu0: percent of the traced window in which no op ran on
the device (time steps with ILU(0))."""


def read(run):
    if run.trace is None or not run.solves:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
