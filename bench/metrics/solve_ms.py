"""solve_ms: the closed loop's elapsed time over the number of its whole
solves, each ended by ``block_until_ready``; a stall between solves
counts."""


def read(run):
    if not run.solves:
        return None
    return run.window_s / len(run.solves) * 1e3
