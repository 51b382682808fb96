"""step_ms.plain: a time stepper's elapsed time over the number of its
whole steps, one solve with no preconditioner each, each ended by
``block_until_ready``; a stall between steps counts."""


def read(run):
    if not run.solves:
        return None
    return run.window_s / len(run.solves) * 1e3
