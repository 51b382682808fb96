"""Pallas TPU kernel: one FUSED Arnoldi step (mat-vec + CGS2) per launch.

An Arnoldi step is the whole hot loop of GMRES (Ioannidis et al. 1906.04051
measure mat-vec + orthogonalization at >90% of parallel GMRES wall-time):

    w  = A @ v_j                     level-2, streams A          (matvec.py)
    h  = mask * (V @ w)   } x2       level-2, streams V           (cgs2.py)
    w' = w - h @ V        } (CGS2)

Run as separate kernels, ``w`` is written to HBM by the mat-vec and
re-read (twice) by each Gram-Schmidt pass, and ``h`` round-trips between
the projection and the update.  This kernel runs the ENTIRE step in one
``pallas_call`` with a two-phase grid:

    phase 0 — grid (nbi, nbj): w[i] += A[i,j] @ v_j[j].  The f32 ``w``
              accumulator is an output block with a CONSTANT index map, so
              it lives in VMEM for the whole kernel and is flushed to HBM
              exactly once, at the end.
    phase 1 — one grid step: both CGS2 passes against the basis V held
              ENTIRELY in VMEM (a (m+1, n) f32 basis is ~m*n*4 bytes —
              128 KiB per 1k of n at m=30 — far under the ~16 MiB core
              budget for every problem the tuner admits), as f32 VPU
              products.  ``h`` and the intermediate ``w'`` never exist in
              HBM at all.

HBM traffic per step: A once, V once, v_j once in; h + w'' once out.  The
unfused kernel pair streams V four times and round-trips w three times —
``benchmarks/kernel_bench.py`` carries the model.  A streams in whatever
dtype it arrives in and upcasts in-register: the solver exploits this for
``compute_dtype=bf16`` by downcasting the padded A ONCE per solve
(core/gmres.py), halving the dominant HBM term while the products
still accumulate at f32/f64.

Feasibility (V must fit in VMEM) is decided by ``tuning.fused_step_fits``;
``core/gmres.py`` falls back to the streaming cgs2 kernel, then to the jnp
reference, when it doesn't hold.  The kernel is single-shard by
construction — the distributed solver keeps its psum boundary outside and
uses the unfused path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning


def _fused_kernel(a_ref, vj_ref, vb_ref, mask_ref, h_ref, w_ref, *, bm, nbi):
    i = pl.program_id(0)
    j = pl.program_id(1)
    acc = w_ref.dtype  # f32 accumulation; f64 for x64 solves

    @pl.when((i == 0) & (j == 0))
    def _init():
        w_ref[...] = jnp.zeros_like(w_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(i < nbi)
    def _matvec():
        # (1, bn) x (bm, bn)^T -> (1, bm) partial of the lane-dense w row,
        # accumulated into its VMEM-resident slice.
        w_ref[:, pl.ds(pl.multiple_of(i * bm, bm), bm)] += (
            jax.lax.dot_general(vj_ref[...], a_ref[...],
                                dimension_numbers=(((1,), (1,)), ((), ())),
                                preferred_element_type=acc))

    @pl.when((i == nbi) & (j == 0))
    def _orthogonalize():
        # Both CGS2 passes on the VMEM-resident basis, as exact f32 VPU
        # products and reductions: an f32 MXU contraction over n would
        # need multi-pass temporaries the size of the basis.  The basis
        # is upcast in-register so bf16 storage still accumulates in full
        # precision.
        v = vb_ref[...].astype(acc)                         # (m1, n)
        mask = mask_ref[...]                                # (m1, 1)
        w = w_ref[...]                                      # (1, n)
        h1 = mask * jnp.sum(v * w, axis=1, keepdims=True)   # project
        w1 = w - jnp.sum(v * h1, axis=0, keepdims=True)     # w - V^T h1
        h2 = mask * jnp.sum(v * w1, axis=1, keepdims=True)  # reorthogonalize
        w2 = w1 - jnp.sum(v * h2, axis=0, keepdims=True)
        h_ref[...] = h1 + h2
        w_ref[...] = w2                           # overwrite the accumulator


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def arnoldi_step(a: jax.Array, v_basis: jax.Array, j, *,
                 block: int | None = None, interpret: bool = False):
    """One fused Arnoldi step: ``w'' , h = cgs2(V, A @ V[j])``.

    a: (n, n) in its storage dtype; v_basis: (m+1, n) row-major basis
    (rows > j are zero); j: traced step index.  Returns
    ``(h, w)`` with h (m+1,) f32 (entries > j zero) and w (n,) f32, the
    UNNORMALIZED reorthogonalized vector — normalization (and the h[j+1]
    breakdown probe) stay outside with the caller, where the distributed
    psum boundary also lives.
    """
    n = a.shape[0]
    m1 = v_basis.shape[0]
    if block is None:
        block = tuning.choose_fused_block(n, a.dtype)
    b = min(block, tuning._round_up(n, tuning.LANE))
    n_pad = tuning._round_up(n, b)
    m1_pad = tuning._round_up(m1, tuning.sublane(v_basis.dtype))

    vj = v_basis[j].astype(a.dtype)
    # mask[i] = 1 for valid basis rows i <= j (padded rows stay masked)
    mask = ((jnp.arange(m1_pad) <= j) & (jnp.arange(m1_pad) < m1)
            ).astype(jnp.float32)

    if n_pad != n or m1_pad != m1:
        a = jnp.pad(a, ((0, n_pad - n), (0, n_pad - n)))
        vj = jnp.pad(vj, (0, n_pad - n))
        v_basis = jnp.pad(v_basis, ((0, m1_pad - m1), (0, n_pad - n)))

    nbi = n_pad // b
    # f32 accumulation for f32/bf16 storage; full f64 for x64 solves (the
    # unfused matvec kernel makes the same choice).
    acc_dtype = jnp.promote_types(a.dtype, jnp.float32)
    kernel = functools.partial(_fused_kernel, bm=b, nbi=nbi)
    h, w = pl.pallas_call(
        kernel,
        grid=(nbi + 1, nbi),
        in_specs=[
            # A tiles stream during phase 0 only; the index map parks on
            # the LAST phase-0 block afterwards so phase 1 triggers no A
            # traffic (parking anywhere else would re-fetch one tile).
            pl.BlockSpec((b, b), lambda i, j: (jnp.minimum(i, nbi - 1),
                                               jnp.where(i < nbi, j,
                                                         nbi - 1))),
            pl.BlockSpec((1, b), lambda i, j: (0, jnp.where(i < nbi, j, 0))),
            # The whole basis is ONE block: fetched once, VMEM-resident.
            pl.BlockSpec((m1_pad, n_pad), lambda i, j: (0, 0)),
            pl.BlockSpec((m1_pad, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((m1_pad, 1), lambda i, j: (0, 0)),
            # w as a lane-dense row: an (n, 1) column would pad each
            # element to a 128-lane row of VMEM.
            pl.BlockSpec((1, n_pad), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m1_pad, 1), acc_dtype),
            jax.ShapeDtypeStruct((1, n_pad), acc_dtype),
        ],
        interpret=interpret,
        name="gmres_arnoldi_fused",
    )(a, vj[None, :], v_basis, mask[:, None].astype(acc_dtype))
    return h[:m1, 0], w[0, :n]


def arnoldi_step_ref(a: jax.Array, v_basis: jax.Array, j):
    """jnp oracle for the fused kernel (matvec + masked CGS2, unnormalized)."""
    from repro.kernels import ref
    m1 = v_basis.shape[0]
    mask = (jnp.arange(m1) <= j).astype(jnp.float32)
    w = ref.matvec(a.astype(jnp.float32), v_basis[j].astype(jnp.float32))
    return ref.cgs2(v_basis.astype(jnp.float32), w, mask)
