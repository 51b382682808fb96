"""precond_apply_roofline: the ILU(0) applies' share of the HBM roofline:
the factor bands, v and the result once per apply, over the device time of
the triangular-sweep kernel."""

from bench import roofline

KERNELS = ("gmres_precond_trisweep",)


def read(run):
    return roofline.kernel_share(run, ("precond",), KERNELS)
