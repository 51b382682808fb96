"""idle_share.solve: percent of the traced window in which no op ran on
the device (one caller's back-to-back solves)."""


def read(run):
    if run.trace is None or not run.solves:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
