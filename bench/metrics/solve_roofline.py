"""solve_roofline: the whole solve's share of the HBM roofline: the least
bytes of every traced solve (operator and orthogonalization) at peak
bandwidth of all the cell's chips, over the traced window."""

from bench import roofline


def read(run):
    if run.trace is None or not run.solves:
        return None
    nbytes = sum(sum(roofline.solve_parts(run, s).values())
                 for s in run.solves if s["traced"])
    return roofline.window_share(run, nbytes)
