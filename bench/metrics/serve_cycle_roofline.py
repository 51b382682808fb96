"""serve_cycle_roofline: the server's lockstep cycles' share of the HBM
roofline: the matrix once per block step (not once per lane) and twice
per cycle for the residuals, each active lane's basis once per step at its
width, over the device time of the mat-vec and batched block
Gram-Schmidt kernels."""

from bench import roofline

KERNELS = ("gmres_matvec", "gmres_block_gs_batched")


def read(run):
    if run.trace is None or not run.traced_cycles:
        return None
    t = run.trace.kernel_s(KERNELS)
    if t <= 0:
        return None
    n = int(run.shapes["n"])
    nbytes = sum(roofline.block_cycle_bytes(n, c) for c in run.traced_cycles)
    return roofline.share(run, nbytes, t)
