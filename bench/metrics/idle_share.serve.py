"""idle_share.serve: percent of the traced window in which no op ran on
the device (open-loop serve cells)."""


def read(run):
    if run.trace is None or not run.requests:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
