"""s-step (communication-avoiding) GMRES — the paper's own citation trail.

The paper cites Chronopoulos' s-step Krylov line (Chronopoulos 1986;
Chronopoulos & Kim 1992; Chronopoulos & Swanson 1996).  The idea: build s
Krylov directions with s mat-vecs and NO per-step inner products, then
orthogonalize the whole block in a CONSTANT number of collective rounds:

    round 1:  C1 = V W^T       (block Gram-Schmidt vs old basis, one psum)
    round 2:  G1 = W'W'^T      (Gram matrix -> CholQR, one psum)
    rounds 3-4: one reorthogonalization pass (CGS2-equivalent stability)

vs. classical Arnoldi's ~4 collective rounds PER STEP (CGS2) or j+2 (MGS).
On a pod where a psum costs axis-latency x log P, collective ROUNDS — not
bytes — bound small-m solves; s-step trades rounds for local (s x s) and
(m x s) matmuls, the MXU's favorite trade.  Round ratio per s steps:
4s -> s + 4 (the s mat-vec all-gathers remain; the matrix-powers kernel
removes their HBM passes too for stencil operators, not for dense A).

Since PR 3 the whole block step is kernel-backed on single-shard solves
(same dispatch contract as the standard cycle got in PR 1):

  powers   kernels/matrix_powers.py — all s normalized powers in ONE
           pallas_call.  Banded/stencil operators keep the band stack
           VMEM-resident (one HBM pass over A for the whole block); dense
           A streams once per power with the normalization reductions
           fused in-register.  Gated by ``tuning.powers_fits``.
  block GS kernels/block_gs.py — each CGS2 pass is one pallas_call with
           the basis VMEM-resident: projection, update and the CholQR
           Gram matrix in-register (V streamed twice per block step
           instead of four times).  Gated by ``tuning.block_gs_fits``.
           The (s, s) Cholesky between passes is replicated algebra and
           stays out here, at the collective boundary.

Row-sharded solves (``axis_name`` under the distributed wrapper's
``tuning.shard_context``) are kernel-backed too since PR 5:

  powers   banded operators run the COMMUNICATION-AVOIDING matrix-powers
           kernel (``matrix_powers.banded_powers_halo``): one ppermute
           halo exchange of width s*halo, all s raw powers per-shard in
           one pallas_call, one psum completing every norm — 2 collective
           rounds per block where the reference pays s all-gathers +
           s psums.  Dense A keeps the per-power all-gather reference
           (dense rows touch every column; nothing to halo).
  block GS the split-phase pair (``block_gs.block_gs_pass_sharded``):
           per-shard project kernel, C psum, per-shard update kernel,
           G psum — the collectives sit exactly where
           ``block_gs_pass_ref`` puts them, so the cycle code is shared.

``kernel_mode() == "ref"``, VMEM-overflowing shapes, and sharded solves
without a shard_context still run the psum-safe jnp references
(``matrix_powers_ref`` / ``block_gs_pass_ref``) — identical arithmetic,
collectives where the kernel outputs sit.

Hessenberg reconstruction (exact, from the power recurrence):
  u_0 = v_k;  A u_{j-1} = sigma_j u_j  (sigma_j = normalization scale)
  orthogonalization gives  u_j = V c[:, j-1] + Q r[:, j-1]
  Let X_j = coefficient vector of u_j in the final basis.  Then
      H X_{j-1} = sigma_j X_j ,   j = 1..s
  i.e. H S1 = S2 with S1 = [X_0..X_{s-1}], S2 = [sigma_j X_j].  Splitting
  H into known columns (< k) and the s new ones and using that S1's rows
  k..k+s-1 form an invertible triangular block S1r:
      H_new = (S2 - H_known S1_masked) @ inv(S1r)
  — all replicated (m x s)-sized algebra, collective-free.

The per-cycle least-squares solve folds the (m+1, m) Hessenberg through
the same incremental Givens QR the standard solver uses (core/givens.py)
— O(m^2) rotations instead of the dense ``lstsq`` SVD path, and the same
replicated, collective-free footprint.

Caveat (inherent to the method, documented since Chronopoulos 1986): the
monomial basis conditions like kappa(A)^s, so practical s is 2..8 in f32;
convergence checks are per-cycle (true residual), not per-step.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import arnoldi, givens
from repro.core.gmres import (Diagnostics, GmresResult, check_precond,
                              classify_residuals, f32_matmuls)
from repro.core.operators import (EXPLICIT_OPERATORS, BandedOperator,
                                  DenseOperator, SparseOperator, as_operator,
                                  with_dtype)


def _leja_perm(s: int) -> tuple:
    """Static Leja-style ordering of the s Chebyshev points.

    Greedy max-product-of-distances on the REFERENCE points
    cos((2k+1) pi / 2s) — pure Python (the point POSITIONS are static even
    though the mapped shift values are traced), so the permutation bakes
    into the trace.  Leja ordering keeps every Newton-basis prefix well
    spread over the interval; consecutive nearby shifts would reintroduce
    the monomial basis's conditioning growth.
    """
    import math
    pts = [math.cos(math.pi * (2 * k + 1) / (2 * s)) for k in range(s)]
    perm = [0]
    remaining = set(range(1, s))
    while remaining:
        nxt = max(remaining, key=lambda j: (
            math.prod(abs(pts[j] - pts[i]) for i in perm), -j))
        perm.append(nxt)
        remaining.discard(nxt)
    return tuple(perm)


def _newton_shifts(op, s: int) -> jax.Array:
    """Newton-basis shifts: Leja-ordered Chebyshev points of A's interval.

    The interval is the Gershgorin bound (one pass over the rows, traced
    — no eigensolve); shifts at the Chebyshev points of [lo, hi] bound
    |prod (A - shift_j)| growth the way the monomial basis (all shifts 0)
    cannot, keeping the power block conditioned far past the kappa^s wall.
    """
    from repro.core.preconditioners import spectral_bounds
    lo, hi = spectral_bounds(op)
    k = jnp.arange(s)
    pts = ((lo + hi) / 2
           + (hi - lo) / 2 * jnp.cos(jnp.pi * (2 * k + 1) / (2 * s)))
    return pts[jnp.asarray(_leja_perm(s))].astype(jnp.float32)


def _make_block_fns(op, n: int, s: int, m1: int, dtype, axis_name,
                    gs: str = "cgs2", precond=None, shifts=None):
    """Trace-time dispatch: (powers_fn, gs_pass_fn, basis_shape, single_reduce).

    Kernel paths need a kernel-capable backend (``tuning.kernel_mode()
    != "ref"``) and a working set that fits VMEM; row-sharded solves
    additionally pick the PER-SHARD variants — the communication-avoiding
    halo powers kernel (banded operators, when the ambient
    ``tuning.shard_context`` supplies the ppermute geometry) and the
    split-phase block-GS pair.  Anything else gets the psum-safe jnp
    references.  Mirrors the ``gs="fused"`` dispatch in core/gmres.py —
    including the pre-padded loop carry: when a block-GS kernel is
    engaged, ``basis_shape`` is the tile-aligned (m1_pad, n_pad) the
    cycle allocates directly, so the basis is never re-padded (a full HBM
    copy) inside the block step.

    Note ``n`` is the LOCAL vector length under sharding, so the VMEM
    fits-checks divide by the shard count — sharding ADMITS kernel-path
    systems the single device could not hold.
    """
    from repro.kernels import block_gs, matrix_powers, spmv, tuning

    mode = tuning.kernel_mode()
    interp = mode == "interpret"
    guard = float(jnp.finfo(dtype).tiny) ** 0.5   # breakdown guard
    # The halo-exchange powers path builds static ppermute permutations,
    # which needs the shard count — only the ambient shard_context (set by
    # core/distributed.py) carries it.
    ctx_sharded = axis_name is not None and tuning.shard_axis() == axis_name
    # Right preconditioning powers B = A M^{-1}: the fused kernels stream
    # A's own storage, so a non-identity M^{-1} takes the reference powers
    # over the composed mat-vec (M^{-1} itself may still be kernel-backed,
    # e.g. the fused Chebyshev apply inside each power).
    identity_pc = precond is None or getattr(precond, "is_identity", False)

    powers_fn = None
    if mode != "ref" and axis_name is None and identity_pc:
        if isinstance(op, BandedOperator):
            halo = max(abs(int(o)) for o in op.offsets)
            if tuning.powers_fits(n, op.bands.dtype, s,
                                  nbands=op.bands.shape[0], halo=halo):
                powers_fn = lambda u0: matrix_powers.banded_powers(
                    op.bands, u0, op.offsets, s, shifts=shifts,
                    interpret=interp)
        elif isinstance(op, SparseOperator):
            width = op.values.shape[1]
            if (tuning.gather_kernel_mode() != "ref"
                    and tuning.ell_powers_fits(n, width, op.values.dtype, s)):
                powers_fn = lambda u0: matrix_powers.ell_powers(
                    op.values, op.cols, u0, s, shifts=shifts,
                    interpret=interp)
        elif isinstance(op, DenseOperator) and shifts is None:
            if tuning.powers_fits(n, op.a.dtype, s):
                block = tuning.choose_powers_block(
                    n, jnp.dtype(op.a.dtype).name, s=s)
                powers_fn = lambda u0: matrix_powers.dense_powers(
                    op.a, u0, s, block=block, interpret=interp)
    elif (mode != "ref" and ctx_sharded and isinstance(op, BandedOperator)
          and identity_pc and shifts is None):
        halo = max(abs(int(o)) for o in op.offsets)
        nshards = tuning.shard_size()
        if (s * halo <= n
                and tuning.powers_fits(n + 2 * s * halo, op.bands.dtype, s,
                                       nbands=op.bands.shape[0], halo=halo)):
            # Bands are loop-invariant: exchange the (s-1)*halo neighbor
            # columns ONCE here (trace top level, outside the restart
            # loop) and zero-pad the outer halo margin.
            bands_ex = spmv.halo_exchange(op.bands.T, (s - 1) * halo,
                                          axis_name, nshards).T
            bands_pad = jnp.pad(bands_ex, ((0, 0), (halo, halo)))
            # Deferred normalization computes RAW powers, whose magnitude
            # grows like ||A||^s — enough to overflow f32 for moderately
            # scaled systems.  Pre-scale by theta >= ||A||_inf (row sums,
            # pmax-completed): the kernel then powers B = A/theta with
            # ||B||_inf <= 1, so no system scale can overflow, and the
            # recurrence is recovered EXACTLY via
            # sigma_j = theta * ||z'_j|| / ||z'_{j-1}|| — this also makes
            # the sharded path scale-invariant by construction (solving
            # c*A, c*b pre-scales c away entirely).
            row_sums = jnp.sum(jnp.abs(op.bands.astype(jnp.float32)),
                               axis=0)
            theta = lax.pmax(jnp.max(row_sums), axis_name)
            theta = jnp.maximum(theta, jnp.asarray(guard, theta.dtype))
            bands_pad = (bands_pad.astype(jnp.float32)
                         / theta).astype(bands_pad.dtype)

            def powers_fn(u0):
                # One neighbor exchange + one psum for ALL s powers: the
                # kernel computes z'_j = (A/theta)^j u0 per-shard, the
                # batched psum completes every ||z'_j||, and u_j / sigma_j
                # follow exactly (see kernels/matrix_powers.py).
                x_halo = spmv.halo_exchange(u0, s * halo, axis_name,
                                            nshards)
                z, nrm_part = matrix_powers.banded_powers_halo(
                    bands_pad, x_halo, op.offsets, s, interpret=interp)
                znorm = jnp.sqrt(lax.psum(nrm_part, axis_name))
                g = jnp.asarray(guard, znorm.dtype)
                prev = jnp.concatenate(
                    [jnp.ones((1,), znorm.dtype), znorm[:-1]])
                sigma = theta.astype(znorm.dtype) * znorm / jnp.maximum(
                    prev, g)
                u = z / jnp.maximum(znorm, g)[:, None]
                return u, sigma
    if powers_fn is None:
        pmatvec = op if identity_pc else (lambda v: op(precond(v)))
        powers_fn = lambda u0: matrix_powers.matrix_powers_ref(
            pmatvec, u0, s, guard, axis_name, shifts=shifts)

    if gs not in ("cgs2", "cgs2_pipelined"):
        raise ValueError(f"gmres_sstep: unknown gs {gs!r}; options: "
                         f"['cgs2', 'cgs2_pipelined']")
    single_reduce = gs == "cgs2_pipelined"
    kernel_gs = mode != "ref" and tuning.block_gs_fits(m1, n, dtype, s=s)
    if single_reduce:
        # ONE stacked psum per pass ([C_hat; M] payload, CholQR Gram
        # recovered against the maintained basis Gram matrix) — 2 rounds
        # per block instead of 4.  The matrix-powers exchange/psum above
        # stays separate: its operand is the RAW power block, whose row
        # scaling would destabilize CholQR if folded into this payload.
        if kernel_gs:
            gs_pass = (lambda v, w, tin, mask, gram:
                       block_gs.block_gs_pass_single_reduce(
                           v, w, tin, mask, gram, axis_name,
                           interpret=interp))
        else:
            gs_pass = (lambda v, w, tin, mask, gram:
                       block_gs.block_gs_pass_single_reduce_ref(
                           v, w, tin, mask, gram, axis_name))
    elif kernel_gs:
        if axis_name is None:
            gs_pass = lambda v, w, tin, mask: block_gs.block_gs_pass(
                v, w, tin, mask, interpret=interp)
        else:
            gs_pass = lambda v, w, tin, mask: block_gs.block_gs_pass_sharded(
                v, w, tin, mask, axis_name, interpret=interp)
    else:
        gs_pass = lambda v, w, tin, mask: block_gs.block_gs_pass_ref(
            v, w, tin, mask, axis_name)
    if kernel_gs:
        m1p, n_pad, _ = tuning.choose_block_gs(m1, n, s,
                                               jnp.dtype(dtype).name)
        basis_shape = (m1p, n_pad)
    else:
        basis_shape = (m1, n)
    return powers_fn, gs_pass, basis_shape, single_reduce


def _block_step(powers_fn, gs_pass, v_basis, h, k_start: int, s: int, eps,
                n: int, gram=None, shifts=None):
    """One s-step block at STATIC offset k_start.

    v_basis: (m1_pad, n_pad) basis carry — live rows/cols are (m+1, n),
    any padding rows/cols are zero (see ``_make_block_fns``).  h: (m+1, m)
    Hessenberg built so far (columns >= k_start are zero).  Returns
    (v_basis with rows k_start+1..k_start+s written,
     h with columns k_start..k_start+s-1 written, gram).

    ``gram`` (single-reduce mode): the maintained (m1_pad, m1_pad) basis
    Gram matrix.  Each pass then pays ONE stacked psum and the CholQR Gram
    is recovered from it; after CholQR the s new basis rows' measured
    inner products extend ``gram`` via

        Gamma_cross = V Q_new^T = (C_hat_2 - Gamma C_2) R_2^{-1}
        Gamma_diag  = Q_new Q_new^T = R_2^{-T} G_2 R_2^{-1}

    — replicated (m x s) algebra, no collective.
    """
    m1p, n_pad = v_basis.shape
    m1 = h.shape[0]                      # live rows: m + 1
    # Two precisions, same split as the standard cycle's compute_dtype
    # path: the STREAMS (basis rows, power block — the O(n) traffic) live
    # in the basis dtype, while the replicated (s x s)/(m x s) algebra —
    # CholQR, Hessenberg recurrence — runs in h's dtype (b.dtype).  The
    # block-GS passes already accumulate in promote(stream, f32), so a
    # bf16 basis halves the streamed bytes without bf16 dot products.
    dtype = v_basis.dtype
    hdt = h.dtype

    # ---- s mat-vecs, no inner products (communication: matvec only) -----
    # One fused launch on the kernel path: A is streamed once for the whole
    # block (banded) or once per power (dense), u_j never round-trips.
    u_cols, sigma = powers_fn(v_basis[k_start, :n])
    u_cols = u_cols.astype(dtype)        # (s, n) power basis; A u_{j-1} =
    sigma = sigma.astype(hdt)            # sigma[j] u_j
    if n_pad != n:                       # cheap (s, n_pad) copy; the BASIS
        u_cols = jnp.pad(u_cols, ((0, 0), (0, n_pad - n)))  # is never re-padded

    # ---- block orthogonalization: CGS2 + CholQR on the whole block ------
    row_mask = (jnp.arange(m1p) <= k_start).astype(dtype)

    def cholqr_factor(g):
        # ridge scaled to the Gram's magnitude: keeps Cholesky PSD even
        # when the block is (near-)degenerate — e.g. the solve converged
        # mid-cycle and the power basis collapsed.  The floor is the
        # scale-free breakdown guard, NOT an absolute 1.0: a system scaled
        # by c must produce the same solve (only a true zero Gram hits it).
        g = g.astype(hdt)
        guard = jnp.asarray(jnp.finfo(hdt).tiny ** 0.5, hdt)
        ridge = jnp.maximum(jnp.max(jnp.diagonal(g)), guard) * eps
        g = g + ridge * jnp.eye(s, dtype=hdt)
        return jnp.linalg.cholesky(g).mT                  # upper

    eye_s = jnp.eye(s, dtype=hdt)
    if gram is None:
        c1, w1, g1 = gs_pass(v_basis, u_cols, eye_s, row_mask)
    else:
        c1, w1, g1, _ = gs_pass(v_basis, u_cols, eye_s, row_mask, gram)
    r1 = cholqr_factor(g1)
    # T = inv(R1^T): folds the CholQR back-substitution (Q1 = R1^{-T} W1)
    # into the second pass's stream instead of a separate (s, n) solve.
    t1 = jax.scipy.linalg.solve_triangular(r1.mT, eye_s, lower=True)
    if gram is None:
        c2, w2, g2 = gs_pass(v_basis, w1.astype(dtype), t1, row_mask)
    else:
        c2, w2, g2, c_hat2 = gs_pass(v_basis, w1.astype(dtype), t1,
                                     row_mask, gram)
    r2 = cholqr_factor(g2)
    # Back-substitute in the algebra dtype (w2 arrives in the passes' f32
    # accumulator); the result is quantized ONCE, where it joins the
    # stored basis stream.
    q = jax.scipy.linalg.solve_triangular(r2.mT, w2.astype(hdt),
                                          lower=True)
    if gram is not None:
        # Extend the maintained Gram matrix by the s rows just built.
        gacc = gram.dtype
        t2 = jax.scipy.linalg.solve_triangular(
            r2.mT.astype(gacc), jnp.eye(s, dtype=gacc), lower=True)
        cross = (c_hat2.astype(gacc)
                 - gram @ c2.astype(gacc)) @ t2.mT       # (m1p, s) X R2^{-1}
        diag = t2 @ g2.astype(gacc) @ t2.mT              # (s, s)
        gram = lax.dynamic_update_slice(gram, cross, (0, k_start + 1))
        gram = lax.dynamic_update_slice(gram, cross.mT, (k_start + 1, 0))
        gram = lax.dynamic_update_slice(gram, diag, (k_start + 1, k_start + 1))
    # Padded basis rows are masked to zero in C, so the Hessenberg algebra
    # below runs at the live (m+1) row count.
    c_tot = (c1[:m1].astype(hdt) + c2[:m1].astype(hdt) @ r1)  # (m1, s)
    r_tot = r2 @ r1                                 # (s, s) upper

    # ---- exact Hessenberg columns from the power recurrence --------------
    # X_j in the (m+1)-row global frame; q_l lives at basis row k_start+1+l.
    xs = [jnp.zeros((m1,), hdt).at[k_start].set(1.0)]     # X_0 = e_k
    for j in range(1, s + 1):
        xj = c_tot[:, j - 1]
        xj = lax.dynamic_update_slice(xj, r_tot[:, j - 1], (k_start + 1,))
        xs.append(xj)
    s1 = jnp.stack(xs[:s], axis=1)                        # (m1, s)
    # Newton basis: A u_{j-1} = sigma_j u_j + shift_j u_{j-1}, so the
    # shifted term rides along in S2 (monomial: shifts identically zero).
    if shifts is None:
        s2_cols = [sigma[j - 1] * xs[j] for j in range(1, s + 1)]
    else:
        sh = shifts.astype(sigma.dtype)
        s2_cols = [sigma[j - 1] * xs[j] + sh[j - 1] * xs[j - 1]
                   for j in range(1, s + 1)]
    s2 = jnp.stack(s2_cols, axis=1)

    s1r = lax.dynamic_slice(s1, (k_start, 0), (s, s))     # invertible tri
    s1_masked = s1 * (jnp.arange(m1) < k_start)[:, None]
    corr = h @ s1_masked[: h.shape[1]]                    # (m1, s)
    h_new = jnp.linalg.solve(s1r.T, (s2 - corr).T).T      # (m1, s)

    v_basis = lax.dynamic_update_slice(v_basis, q.astype(dtype),
                                       (k_start + 1, 0))
    h = lax.dynamic_update_slice(h, h_new, (0, k_start))
    return v_basis, h, gram


@f32_matmuls
def gmres_sstep(a, b, x0=None, *, s: int = 4, blocks: int = 5,
                tol: float = 1e-5, max_restarts: int = 30,
                axis_name: Optional[str] = None,
                gs: str = "cgs2", history: int = 8,
                precond: Optional[Callable] = None,
                basis: str = "monomial",
                compute_dtype=None) -> GmresResult:
    """Restarted s-step GMRES(m = s * blocks).

    ``a`` may be any operator ``gmres`` accepts; ``BandedOperator`` /
    ``DenseOperator`` systems run the block step through the Pallas
    matrix-powers + block-GS kernels when single-shard and VMEM-sized
    (see module docstring), degrading to the jnp reference otherwise.
    The per-cycle least-squares solve folds the replicated (m+1, m)
    Hessenberg through incremental Givens QR — tiny next to the mat-vecs
    and collective-free.

    ``gs``: "cgs2" (the split-phase block passes — 4 psums per block when
    sharded) | "cgs2_pipelined" (single-reduce passes: each pass's C and
    Gram reductions cross shards as ONE stacked payload, with the CholQR
    Gram recovered against a maintained basis Gram matrix — 2 psums per
    block; with the banded CA powers path that is 4 collective rounds per
    s steps total).  There is no cross-block mat-vec pipelining here: the
    power basis of block k+1 starts from the LAST orthonormal vector of
    block k, a true dependency the standard cycle's depth-1 trick cannot
    break.

    ``precond``: right preconditioner ``v -> M^{-1} v`` (None = identity).
    The power block is built over ``B = A M^{-1}`` through the reference
    powers (the apply itself may be kernel-backed, e.g. the fused
    Chebyshev recurrence) and the cycle update un-preconditions:
    ``x += M^{-1} (y V)``.  ``basis``: "monomial" | "newton" — Newton uses
    Leja-ordered Chebyshev-point shifts of A's Gershgorin interval in the
    SAME one-pass powers kernels (``shifts=``), keeping the block
    conditioned past the monomial kappa^s wall (sharded banded solves keep
    the monomial CA halo kernel; newton there runs the per-power psum
    reference).

    ``compute_dtype``: storage dtype for the STREAMED arrays — the basis
    carry and the power block — mirroring the standard cycle's option
    (PR 3's fused path).  ``bf16`` halves basis traffic AND, for explicit
    operators, downcasts the operand stream of A inside the power block
    (the matrix-powers / SpMV kernels accumulate in f32 in-register); the
    replicated CholQR/Hessenberg/Givens algebra and the restart-boundary
    residual recompute stay in ``b.dtype``, so tolerance checks are
    honest.  None keeps everything in ``b.dtype``.
    """
    matvec = as_operator(a)
    if x0 is None:
        x0 = jnp.zeros_like(b)
    n = b.shape[0]
    dtype = b.dtype
    basis_dtype = dtype if compute_dtype is None else jnp.dtype(compute_dtype)
    eps = jnp.asarray(jnp.finfo(dtype).eps * 100, dtype)   # relative factor
    guard = jnp.asarray(jnp.finfo(dtype).tiny ** 0.5, dtype)
    m = s * blocks
    bnorm = arnoldi.norm(b, axis_name)
    tol_abs = tol * bnorm
    if basis not in ("monomial", "newton"):
        raise ValueError(f"gmres_sstep: unknown basis {basis!r}; options: "
                         f"['monomial', 'newton']")
    check_precond(precond)
    shifts = _newton_shifts(matvec, s) if basis == "newton" else None
    identity_pc = precond is None or getattr(precond, "is_identity", False)
    # A compute dtype narrower than A's storage also downcasts the A
    # stream inside the power block — the original operator is kept for
    # the restart-boundary residual (full-precision convergence checks).
    power_op = matvec
    if (isinstance(matvec, EXPLICIT_OPERATORS)
            and jnp.dtype(basis_dtype).itemsize
            < jnp.dtype(matvec.dtype).itemsize):
        power_op = with_dtype(matvec, basis_dtype)
    powers_fn, gs_pass, basis_shape, single_reduce = _make_block_fns(
        power_op, n, s, m + 1, basis_dtype, axis_name, gs, precond=precond,
        shifts=shifts)
    gacc = jnp.promote_types(dtype, jnp.float32)

    def cycle(x):
        r = b - matvec(x)
        beta = arnoldi.norm(r, axis_name)
        v = jnp.zeros(basis_shape, basis_dtype).at[0, :n].set(
            (r / jnp.maximum(beta, guard)).astype(basis_dtype))
        h = jnp.zeros((m + 1, m), dtype)
        # Identity init is exact where it matters: rows beyond the current
        # block are only ever touched against zero (masked) columns.
        gram = jnp.eye(basis_shape[0], dtype=gacc) if single_reduce else None
        for blk in range(blocks):                  # static offsets
            v, h, gram = _block_step(powers_fn, gs_pass, v, h, blk * s, s,
                                     eps, n, gram, shifts=shifts)

        # Fold the m Hessenberg columns through incremental Givens QR.  The
        # ``done`` latch mirrors the standard solver's cycle masking: once
        # the LS residual meets tol or a subdiagonal collapses (the Krylov
        # space is exhausted — e.g. b an eigenvector), remaining columns
        # fold as identity with y_j = 0, keeping R nonsingular where the
        # old dense ``lstsq`` relied on the SVD's min-norm behavior.
        def fold(j, carry):
            st, done = carry
            col = lax.dynamic_slice(h, (0, j), (m + 1, 1))[:, 0]
            st = givens.update(st, col, j, active=jnp.logical_not(done))
            # Relative breakdown probe: a subdiagonal that has collapsed
            # against its own column (an all-zero column included) marks
            # the Krylov space exhausted, at ANY system scale.
            happy = jnp.abs(col[j + 1]) <= eps * jnp.max(jnp.abs(col))
            done = done | (givens.residual_norm(st, j) <= tol_abs) | happy
            return st, done

        giv, _ = lax.fori_loop(
            0, m, fold, (givens.init(m, beta, dtype), beta <= tol_abs))
        y = givens.solve(giv)
        dx = y @ v[:m, :n].astype(dtype)
        # Right preconditioning: the basis spans the M^{-1}-Krylov space,
        # so the update un-preconditions (x solves A x = b, untransformed).
        return x + (dx if identity_pc else precond(dx))

    def cond(carry):
        _, beta, it, _ = carry
        return (beta > tol_abs) & (it < max_restarts)

    def body(carry):
        x, _, it, hist = carry
        x = cycle(x)
        beta = arnoldi.norm(b - matvec(x), axis_name)
        hist = jnp.roll(hist, -1).at[-1].set(beta)
        return x, beta, it + 1, hist

    beta0 = arnoldi.norm(b - matvec(x0), axis_name)
    # Same bounded residual ring as ``gmres`` (see core/gmres.Diagnostics):
    # chronological, inf left-padding, seeded with the entry residual.
    hist0 = jnp.full((history,), jnp.inf, beta0.dtype).at[-1].set(beta0)
    x, beta, it, hist = lax.while_loop(
        cond, body, (x0, beta0, jnp.zeros((), jnp.int32), hist0))
    converged = beta <= tol_abs
    diags = Diagnostics(
        status=classify_residuals(hist, converged=converged),
        residual_history=hist,
        history_len=jnp.minimum(it + 1, history).astype(jnp.int32),
    )
    return GmresResult(x=x, residual=beta, restarts=it, converged=converged,
                       inner_steps=it * m,
                       done=converged | (it >= max_restarts),
                       diagnostics=diags)
