"""arnoldi_step_roofline: the dense solve's share of the HBM roofline in
its kernels: the matrix once per step and per residual, the basis once per
step at its width, over the device time of the fused Arnoldi step and the
mat-vec kernel."""

from bench import roofline

KERNELS = ("gmres_arnoldi_fused", "gmres_matvec")


def read(run):
    return roofline.kernel_share(run, ("operator", "orthogonalize"), KERNELS)
