"""ilu0_step_roofline: the whole time step's share of the HBM roofline
(with ILU(0)): the least bytes of every traced step's solve at peak
bandwidth of all the cell's chips, over the traced window."""

from bench import roofline


def read(run):
    if run.trace is None or not run.solves:
        return None
    nbytes = sum(sum(roofline.solve_parts(run, s).values())
                 for s in run.solves if s["traced"])
    return roofline.window_share(run, nbytes)
