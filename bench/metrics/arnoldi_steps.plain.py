"""arnoldi_steps.plain: Arnoldi steps per time step (with no preconditioner), the
mean over the window of the program's own ``GmresResult.inner_steps``."""


def read(run):
    if not run.solves:
        return None
    return sum(s["inner_steps"] for s in run.solves) / len(run.solves)
