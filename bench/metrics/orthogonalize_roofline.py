"""orthogonalize_roofline: the pipelined single-reduce orthogonalization's
share of the HBM roofline: the basis twice per step at its width and five
vectors, over the device time of the payload and update kernels."""

from bench import roofline

KERNELS = ("gmres_gs_project_norm", "gmres_gs_update")


def read(run):
    return roofline.kernel_share(run, ("orthogonalize",), KERNELS)
