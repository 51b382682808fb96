"""Restarted GMRES(m) — jit-compilable, batched, early-stop-masked.

Faithful to the paper's algorithm (Kelley 1995 listing, section 3):

  1.  r0 = b - A x0, v1 = r0/||r0||
  2.  m Arnoldi steps building V_m, H~_m          (arnoldi.py)
  8.  y_m = argmin || beta e1 - H~_m y ||         (givens.py, incremental QR)
  9.  restart with x_m = x0 + V_m y_m until ||r|| < eps

Shape-static by construction: the inner loop always runs ``m`` steps with
converged / broken-down steps masked to no-ops (identity Givens columns,
zeroed g entries), so the whole restarted solve is ONE ``jax.jit`` program —
the ``gpuR``/vcl "everything device-resident" strategy from the paper, taken
to its logical conclusion: not a single scalar leaves the device between
restarts.

The hot loop is kernel-backed: with ``gs="fused"`` and a dense operator the
whole Arnoldi step (mat-vec + CGS2) is ONE ``pallas_call``
(kernels/arnoldi_fused.py) with w and h resident in VMEM; ``gs="cgs2_fused"``
runs the streaming fused Gram-Schmidt kernel (kernels/cgs2.py); and the
``backend="pallas"`` operators route every mat-vec through the tiled
kernels (kernels/matvec.py dense, kernels/spmv.py ELL/banded).  Each path
degrades gracefully — interpret mode on CPU, jnp reference where Pallas is
unavailable or shapes don't fit VMEM.

The same inner cycle, handed an ``axis_name``, becomes the shard_map
distributed solver (core/distributed.py) — and since PR 5 it stays
kernel-backed there too: under the distributed wrapper's
``tuning.shard_context`` the operators run their halo-exchange /
all-gather per-shard mat-vecs and ``gs="cgs2_fused"`` runs the
split-phase CGS2 kernel pair with the h psum between the phases.  There
is exactly ONE cycle implementation for local and distributed solves.

Every cycle names its parts with ``jax.named_scope``, which lands in the
compiled program's ``op_name`` metadata (no run-time cost): ``operator``
(each application of A with the staging of its operand, the pads of A
included), ``precond`` (each M^{-1} apply), ``orthogonalize`` (the
Gram-Schmidt scheme and the basis reads and writes), ``arnoldi_step``
(the fused single-kernel step, operator and orthogonalization together),
``lsq`` (the Givens updates, the triangular solve and the solution
update) and ``residual`` (the true-residual recompute; its mat-vec is
``residual/operator``).  A profiler trace's device ops map to them.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import arnoldi, givens
from repro.core.operators import (EXPLICIT_OPERATORS, DenseOperator,
                                  as_operator)


# --------------------------------------------------------------------------
# Cycle-level health taxonomy (the detection layer of core/recovery.py's
# degradation ladder; see docs/robustness.md).  Codes are int32 so the
# classification runs inside jit and crosses shard_map as a replicated
# scalar.
# --------------------------------------------------------------------------
HEALTHY = 0     # converging (or already converged)
NAN_INF = 1     # residual left the reals — poisoned arithmetic
STAGNATED = 2   # no meaningful decrease across the history window
BREAKDOWN = 3   # residual GREW across a cycle (orthogonalization collapse)
STATUS_NAMES = ("HEALTHY", "NAN_INF", "STAGNATED", "BREAKDOWN")

# Scale-relative thresholds, matching the repo's invariance contract
# (c·A, c·b must classify identically to A, b — both are pure ratios).
BREAKDOWN_GROWTH = 10.0    # beta_k > 10 * beta_{k-1}  ->  BREAKDOWN
STAGNATION_RTOL = 0.99     # beta_k >= 0.99 * beta_{k-window}  ->  STAGNATED


class Diagnostics(NamedTuple):
    """Post-solve health report attached to ``GmresResult.diagnostics``.

    ``residual_history`` is a bounded ring of TRUE per-cycle residual norms
    in chronological order — oldest first, current residual last, ``inf``
    padding on the left until the window fills.  Entry 0 of a full window is
    the residual ``window - 1`` cycles ago; the seed entry is ``||b - A
    x0||`` so examples get a convergence trace without re-solving.
    """
    status: jax.Array            # int32: HEALTHY / NAN_INF / ...
    residual_history: jax.Array  # (window,) chronological, inf-padded
    history_len: jax.Array       # int32: valid trailing entries


def classify_residuals(history, *, converged) -> jax.Array:
    """Classify a residual-history ring into a health status code.

    Pure and jit-safe; ``history`` is the chronological inf-padded ring
    described on ``Diagnostics`` (last entry = current residual).  The
    priority order NAN_INF > BREAKDOWN > STAGNATED matters: a NaN residual
    also fails the growth compare, and a breakdown window is trivially
    stagnant.  A converged solve is HEALTHY regardless of its path.
    """
    history = jnp.asarray(history)
    last = history[-1]
    prev = history[-2] if history.shape[0] > 1 else last
    oldest = history[0]
    nan_inf = jnp.logical_not(jnp.isfinite(last))
    breakdown = (jnp.isfinite(prev) & (last > BREAKDOWN_GROWTH * prev)
                 & jnp.logical_not(converged))
    stagnated = (jnp.isfinite(oldest) & (last >= STAGNATION_RTOL * oldest)
                 & jnp.logical_not(converged))
    code = jnp.where(
        nan_inf, NAN_INF,
        jnp.where(breakdown, BREAKDOWN,
                  jnp.where(stagnated, STAGNATED, HEALTHY)))
    return code.astype(jnp.int32)


class GmresResult(NamedTuple):
    x: jax.Array
    residual: jax.Array      # final true residual norm ||b - A x||
    restarts: jax.Array      # number of restart cycles executed
    converged: jax.Array     # bool
    inner_steps: jax.Array   # total Arnoldi steps actually active
    # converged OR restart budget exhausted.  Scalar for ``gmres``; per-lane
    # for ``gmres_batched``, where a True/False split reads as
    # retired-converged vs retired-FAILED — the distinction the serving
    # layer (repro/serve) keys lane retirement on.
    done: jax.Array = None
    # Cycle-level health report (``Diagnostics``) for the scalar solvers
    # (``gmres`` / ``gmres_sstep``); None on the batched path, where the
    # serving layer owns per-lane health.
    diagnostics: Optional[Diagnostics] = None

    @property
    def residual_history(self):
        """Convergence trace shortcut: ``diagnostics.residual_history``."""
        return None if self.diagnostics is None \
            else self.diagnostics.residual_history


def f32_matmuls(solver: Callable) -> Callable:
    """Trace ``solver`` with every matmul at full f32 precision.

    On TPU an f32 matmul at the default precision feeds the MXU bf16
    operands, in XLA and in Pallas kernels alike; a Krylov solve then
    stalls near bf16's epsilon (a dense N = 10,000 GMRES plateaued at a
    4e-3 relative residual on a v5e chip).  The precision is fixed when a
    dot is traced, so the solver entry points trace their bodies inside
    ``jax.default_matmul_precision("highest")``.  CPU matmuls are f32
    either way.
    """

    @functools.wraps(solver)
    def traced_at_f32(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return solver(*args, **kwargs)

    return traced_at_f32


class _CycleState(NamedTuple):
    v: jax.Array             # (m+1, n_local) Krylov basis, row-major
    giv: givens.GivensState
    done: jax.Array          # latched convergence/breakdown flag
    steps: jax.Array         # active step count (== next j)


# Scheme names that request kernel-backed execution; the jnp scheme each one
# degrades to when the kernel path is unavailable (block solver, non-Pallas
# backend, sharded basis, ...).
_FUSED_STEP_SCHEMES = ("fused", "arnoldi_fused")
_SCHEME_FALLBACK = {"fused": "cgs2", "arnoldi_fused": "cgs2",
                    "cgs2_fused": "cgs2", "cgs2_pipelined": "cgs2"}


def _make_step_fn(matvec, precond, gs: str, axis_name, *, identity_precond,
                  m: int, n: int, basis_dtype) -> Callable:
    """Build ``step_fn(v_basis, j) -> ArnoldiStep`` for the inner loop.

    ``gs="fused"`` asks for the single-pallas_call Arnoldi step: mat-vec +
    both CGS2 passes in one kernel, basis VMEM-resident.  That needs a dense
    unpreconditioned single-shard operator and enough VMEM; anything else
    degrades to the streaming cgs2 kernel ("cgs2_fused"), which itself
    degrades to the jnp reference (see arnoldi.cgs2_fused_step).
    """
    if gs in _FUSED_STEP_SCHEMES:
        from repro.kernels import tuning

        mode = tuning.kernel_mode()
        # ``compute_dtype`` narrower than A's storage (bf16 basis over an
        # f32 matrix) downcasts the A STREAM too: tiles enter the kernel at
        # half width and accumulate f32 in-register, halving the dominant
        # HBM term of the step.  The per-restart true residual still runs
        # through the operator's own full-precision matvec, so reported
        # convergence stays trustworthy.
        a_dtype = matvec.a.dtype if isinstance(matvec, DenseOperator) else None
        if (a_dtype is not None
                and tuning.itemsize(basis_dtype) < tuning.itemsize(a_dtype)):
            a_dtype = basis_dtype
        if (axis_name is None and identity_precond and mode != "ref"
                and isinstance(matvec, DenseOperator)
                and tuning.fused_step_fits(m + 1, n, basis_dtype,
                                           a_dtype=a_dtype)):
            from repro.kernels import arnoldi_fused

            interp = mode == "interpret"
            # Pre-pad ONCE to the kernel's tile grid: the basis is
            # loop-carried, so padding it inside the step would copy the
            # whole (m+1, n) array every inner iteration.  The cycle
            # allocates the carry at ``basis_shape`` directly (padded rows
            # and columns stay zero and are masked in the kernel); A is
            # padded here, outside the loop.
            block = tuning.choose_fused_block(n, a_dtype)
            n_pad = tuning._round_up(n, block)
            m1_pad = tuning._round_up(m + 1, tuning.sublane(basis_dtype))
            with jax.named_scope("operator"):
                a_pad = jnp.pad(matvec.a.astype(a_dtype),
                                ((0, n_pad - n), (0, n_pad - n)))

            def fused_step(v_basis, j):
                with jax.named_scope("arnoldi_step"):
                    h, w = arnoldi_fused.arnoldi_step(a_pad, v_basis, j,
                                                      block=block,
                                                      interpret=interp)
                    return arnoldi.finalize(w, h[:m + 1], j, None)

            fused_step.basis_shape = (m1_pad, n_pad)
            return fused_step
        gs = "cgs2_fused"

    gs_step = arnoldi.step(gs)

    def step(v_basis, j):
        with jax.named_scope("orthogonalize"):
            vj = v_basis[j]
        with jax.named_scope("precond"):
            z = precond(vj)
        with jax.named_scope("operator"):
            w = matvec(z)
        with jax.named_scope("orthogonalize"):
            return gs_step(v_basis, w, j, axis_name)

    return step


def _gmres_cycle(step_fn, x0, r0, beta, m, tol_abs, precond, basis_dtype):
    """One restart cycle: up to m Arnoldi steps + triangular solve.

    The inner loop is a ``while_loop`` with TRUE early exit, not a masked
    fixed-trip fori_loop: on fast-converging systems a fixed m=30 cycle
    would waste (m - k) full mat-vec + orthogonalization steps as masked
    no-ops (SSPerf: measured 6x overhead at k~5).  Early exit keeps the
    whole solve one XLA program (vmap of while_loop is supported) while
    doing only the work the mathematics needs.

    ``basis_dtype`` is the Krylov-basis storage dtype (the ``compute_dtype``
    knob): bf16 storage halves the V stream while every reduction still
    accumulates in f32, and the true residual recomputed per restart bounds
    the error.
    """
    n = x0.shape[0]
    dtype = x0.dtype
    eps = jnp.asarray(jnp.finfo(dtype).tiny ** 0.5, dtype)

    # Kernel-backed steps may ask for a tile-aligned carry (see
    # _make_step_fn); padded rows/columns are zero and never touched.
    basis_rows, basis_cols = getattr(step_fn, "basis_shape", (m + 1, n))
    with jax.named_scope("orthogonalize"):
        v0 = (r0 / jnp.maximum(beta, eps)).astype(basis_dtype)
        v = jnp.zeros((basis_rows, basis_cols), basis_dtype).at[0, :n].set(
            v0)
    with jax.named_scope("lsq"):
        giv = givens.init(m, beta, dtype)
    state = _CycleState(
        v=v,
        giv=giv,
        done=beta <= tol_abs,
        steps=jnp.zeros((), jnp.int32),
    )

    def cond(s: _CycleState):
        return jnp.logical_not(s.done) & (s.steps < m)

    def body(s: _CycleState):
        j = s.steps
        # --- Arnoldi: w = A M^{-1} v_j, orthogonalize against V[:j+1] ---
        st = step_fn(s.v, j)
        with jax.named_scope("orthogonalize"):
            v = s.v.at[j + 1, :st.v_next.shape[0]].set(
                st.v_next.astype(basis_dtype))
        # --- Givens: fold column j, track LS residual ---
        with jax.named_scope("lsq"):
            giv = givens.update(s.giv, st.h.astype(dtype), j,
                                active=jnp.asarray(True))
            resid = givens.residual_norm(giv, j)
            happy = st.h_last <= eps * 100.0
            done = (resid <= tol_abs) | happy
        return _CycleState(v=v, giv=giv, done=done, steps=j + 1)

    state = lax.while_loop(cond, body, state)
    with jax.named_scope("lsq"):
        y = givens.solve(state.giv, state.steps)      # zeros past early stop
        dx = y @ state.v[:m, :n].astype(dtype)        # V^T y with row basis
    with jax.named_scope("precond"):
        dx = precond(dx)
    with jax.named_scope("lsq"):
        x = x0 + dx
    return x, state.steps


# --------------------------------------------------------------------------
# Pipelined single-reduce cycle (gs="cgs2_pipelined")
# --------------------------------------------------------------------------
class _PipelinedState(NamedTuple):
    v: jax.Array             # (m+1, n_local) Krylov basis, row-major
    z: jax.Array             # op(v_j): the pipelined raw mat-vec carry
    hraw: jax.Array          # (m+1, m) raw Hessenberg columns (recurrence)
    gram: jax.Array          # (m+1, m+1) maintained basis Gram matrix
    giv: givens.GivensState
    done: jax.Array
    steps: jax.Array


def _make_pipelined_fns(matvec, precond, axis_name, *, m: int, n: int,
                        basis_dtype):
    """Build ``(op, update)`` for the pipelined cycle.

    ``op`` is the preconditioned operator A M^{-1}; ``update`` computes
    ``w - h @ V`` through the streaming update kernel under the standard
    dispatch policy (compiled / interpret / jnp reference, VMEM-gated).
    The payload half dispatches inside ``arnoldi.sr_payload``.
    """
    from repro.kernels import tuning

    mode = tuning.kernel_mode()
    dtn = jnp.dtype(basis_dtype).name
    if mode != "ref" and tuning.gs_payload_fits(m + 1, n, dtn):
        from repro.kernels import cgs2 as cgs2_k

        bn = tuning.choose_gs_block(m + 1, n, dtn)
        interp = mode == "interpret"

        def update(v_basis, w, h):
            return cgs2_k.gs_update(v_basis, w, h, block_n=bn,
                                    interpret=interp)
    else:

        def update(v_basis, w, h):
            acc = jnp.promote_types(w.dtype, jnp.float32)
            out = w.astype(acc) - h.astype(acc) @ v_basis.astype(acc)
            return out.astype(w.dtype)

    def op(zv):
        with jax.named_scope("precond"):
            z = precond(zv)
        with jax.named_scope("operator"):
            return matvec(z)

    return op, update


def _gmres_cycle_pipelined(op, update, x0, r0, beta, m, tol_abs, precond,
                           basis_dtype, axis_name):
    """One restart cycle of depth-1 pipelined single-reduce GMRES.

    Per Arnoldi step the body pays exactly ONE collective — the fused
    ``sr_payload`` psum — and issues it BEFORE the step-(j+1) mat-vec,
    consuming it after (Ghysels & Vanroose 2013 style depth-1 pipelining):

        payload_j = psum([mask*(V@z_j); z_j.z_j])     <- the only collective
        u         = op(z_j)                           <- independent: XLA's
                                                         latency-hiding
                                                         scheduler overlaps
                                                         it with the psum
        recover h_tot, ||w''||, Gram column from payload_j (replicated)
        v_{j+1}   = (z_j - h_tot @ V) / ||w''||
        z_{j+1}   = (u - (H h_lt) @ V - h_tot[j] z_j) / ||w''||

    The z recurrence uses op(v_i) = V @ H[:, i] (the Arnoldi relation) so
    the next mat-vec never waits for v_{j+1}: the basis never sees ``op``
    on the critical path behind the reduction.  Cost: one speculative
    mat-vec per cycle is wasted at the final step (the pipeline bubble),
    and the correction inherits recurrence rounding — bounded by the TRUE
    residual recompute at every restart (the +-1-restart parity contract).

    Scale-invariant by construction: z scales linearly with the system, the
    recovered norm with z, and the breakdown probe compares ||w''|| against
    eps * ||z|| (relative), matching PR 3's invariance contract.
    """
    n = x0.shape[0]
    dtype = x0.dtype
    tiny = jnp.asarray(jnp.finfo(dtype).tiny ** 0.5, dtype)
    eps_rel = jnp.asarray(jnp.finfo(dtype).eps * 100.0, dtype)
    acc = jnp.promote_types(dtype, jnp.float32)

    with jax.named_scope("orthogonalize"):
        v0 = (r0 / jnp.maximum(beta, tiny)).astype(dtype)
        v = jnp.zeros((m + 1, n), basis_dtype).at[0].set(
            v0.astype(basis_dtype))
        hraw = jnp.zeros((m + 1, m), dtype)
        gram = jnp.eye(m + 1, dtype=acc)
    with jax.named_scope("lsq"):
        giv = givens.init(m, beta, dtype)
    state = _PipelinedState(
        v=v,
        z=op(v0),                               # pipeline prologue mat-vec
        hraw=hraw,
        gram=gram,
        giv=giv,
        done=beta <= tol_abs,
        steps=jnp.zeros((), jnp.int32),
    )

    def cond(s: _PipelinedState):
        return jnp.logical_not(s.done) & (s.steps < m)

    def body(s: _PipelinedState):
        j = s.steps
        # --- issue the ONE collective of this step ---
        with jax.named_scope("orthogonalize"):
            payload = arnoldi.sr_payload(s.v, s.z, j, axis_name)
        # --- the next mat-vec, independent of the psum result ---
        u = op(s.z)
        # --- consume: replicated recovery of both passes + norm ---
        with jax.named_scope("orthogonalize"):
            h_tot, s_norm, zeta, gram = arnoldi.sr_recover(payload, s.gram,
                                                           j)
            h_tot = h_tot.astype(dtype)
            s_d = s_norm.astype(dtype)
            sg = jnp.maximum(s_d, tiny)
            w2 = update(s.v, s.z, h_tot)        # w'' = z - h_tot @ V
            v_next = w2 / sg
            # correct the speculative mat-vec onto v_{j+1} via the
            # recurrence
            lt = (jnp.arange(m) < j).astype(dtype)
            c_vec = s.hraw @ (h_tot[:m] * lt)   # (m+1,) basis coefficients
            z_next = (update(s.v, u, c_vec) - h_tot[j] * s.z) / sg

            v = s.v.at[j + 1].set(v_next.astype(basis_dtype))
            hcol = h_tot.at[j + 1].set(s_d)
            hraw = s.hraw.at[:, j].set(hcol)
        with jax.named_scope("lsq"):
            giv = givens.update(s.giv, hcol, j, active=jnp.asarray(True))
            resid = givens.residual_norm(giv, j)
            happy = s_d <= eps_rel * jnp.sqrt(zeta).astype(dtype)
            done = (resid <= tol_abs) | happy
        return _PipelinedState(v=v, z=z_next, hraw=hraw, gram=gram, giv=giv,
                               done=done, steps=j + 1)

    state = lax.while_loop(cond, body, state)
    with jax.named_scope("lsq"):
        y = givens.solve(state.giv, state.steps)
        dx = y @ state.v[:m].astype(dtype)
    with jax.named_scope("precond"):
        dx = precond(dx)
    with jax.named_scope("lsq"):
        x = x0 + dx
    return x, state.steps


def _pow2_scale(b, axis_name=None):
    """A power of two at or above ``max |b|`` (1 for a zero, inf or nan
    ``b``) and its reciprocal, both built from their bits.

    ``gmres`` solves at this scale: a power of two scales exactly, so a
    right-hand side in the normal range solves bit for bit as it would
    unscaled, while one far from 1 (a decayed time-stepper state near
    1e-17) stays clear of the cycles' absolute breakdown guards
    (``tiny ** 0.5``) and of squared norms that underflow.
    """
    peak = jnp.max(jnp.abs(b))
    if axis_name is not None:
        peak = lax.pmax(peak, axis_name)
    info = jnp.finfo(peak.dtype)
    bias = info.maxexp - 1
    bits = jnp.dtype(f"int{8 * info.dtype.itemsize}")
    _, e = jnp.frexp(peak)
    e = jnp.clip(e, 2 - bias, bias - 1).astype(bits)   # both stay normal

    def pow2(k):
        return lax.bitcast_convert_type((k + bias) << info.nmant, peak.dtype)

    return pow2(e), pow2(-e)


@f32_matmuls
def gmres(
    a,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    m: int = 30,
    tol: float = 1e-5,
    max_restarts: int = 50,
    gs: str = "cgs2",
    precond: Optional[Callable] = None,
    axis_name: Optional[str] = None,
    compute_dtype=None,
    history: int = 8,
) -> GmresResult:
    """Right-preconditioned restarted GMRES(m).

    Args:
      a: the system operator — a dense (n, n) array, any operator from
        ``core.operators`` (``DenseOperator``, ``SparseOperator``,
        ``BandedOperator``, ``FunctionOperator``), or a bare matvec
        callable.  Operators carry their own ``backend=`` ("jnp" |
        "pallas") mat-vec dispatch; the solver never inspects the storage
        format, so sparse systems need no solver-side changes.  With
        ``axis_name`` set, ``a`` maps a LOCAL shard to a LOCAL shard and all
        reductions psum over that mesh axis.
      b: right-hand side, shape (n,) (local shard under ``axis_name``).
      x0: initial guess (zeros by default).
      m: restart length (Krylov subspace dimension per cycle).
      tol: relative residual target, ||b - Ax|| <= tol * ||b||.
      max_restarts: restart-cycle budget.
      gs: "cgs" (paper listing) | "mgs" (serial standard) | "cgs2" (TPU
        path) | "cgs2_fused" (Pallas streaming GS kernel single-shard;
        the split-phase project/psum/update kernel pair when row-sharded)
        | "fused" (whole Arnoldi step in one Pallas kernel; needs an
        unpreconditioned single-shard ``DenseOperator`` and a basis that
        fits VMEM — degrades to "cgs2_fused" otherwise, which itself
        degrades to "cgs2" where Pallas is unavailable)
        | "cgs2_pipelined" (single-reduce CGS2 with depth-1 pipelining:
        ONE fused psum per Arnoldi step — projection coefficients and the
        norm contribution in one stacked payload — issued before and
        consumed after the next mat-vec so the collective hides behind
        compute; kernel-backed payload/update halves with the same
        compiled/interpret/jnp-ref dispatch, psum-safe reference when
        unfit).
      precond: right preconditioner M^{-1} as a callable (identity default).
      axis_name: mesh axis for the row-sharded distributed solve.
      compute_dtype: Krylov-basis storage dtype (e.g. ``jnp.bfloat16``)
        — halves basis HBM traffic; reductions still accumulate in f32 and
        the per-restart true-residual recompute bounds the rounding error.
        On the ``gs="fused"`` path a compute dtype narrower than A's
        storage also downcasts the A STREAM (tiles enter the kernel at the
        narrow width, accumulate f32 in-register).
      history: length of the bounded per-cycle residual-history ring kept
        on ``result.diagnostics`` (static).  Doubles as the stagnation
        window: STAGNATED means the residual failed to drop by at least
        ``1 - STAGNATION_RTOL`` across the last ``history`` cycles.

    Returns GmresResult; residual is the TRUE residual recomputed from x,
    ``diagnostics`` the cycle-level health report (see ``Diagnostics``).
    The solve runs on ``b`` and ``x0`` divided by a power of two near
    ``max |b|`` (``_pow2_scale``), so a tiny or huge ``b`` converges as
    its unit-scale copy would.
    """
    matvec = as_operator(a)
    if x0 is None:
        x0 = jnp.zeros_like(b)
    check_precond(precond)
    scale, inv = _pow2_scale(b, axis_name)
    b, x0 = b * inv, x0 * inv
    identity_precond = (precond is None
                        or getattr(precond, "is_identity", False))
    if precond is None:
        precond = lambda v: v
    basis_dtype = b.dtype if compute_dtype is None else compute_dtype

    pipelined = gs == "cgs2_pipelined"
    if pipelined:
        op_fn, update_fn = _make_pipelined_fns(
            matvec, precond, axis_name, m=m, n=b.shape[0],
            basis_dtype=basis_dtype)
    else:
        step_fn = _make_step_fn(matvec, precond, gs, axis_name,
                                identity_precond=identity_precond, m=m,
                                n=b.shape[0], basis_dtype=basis_dtype)

    with jax.named_scope("residual"):
        bnorm = arnoldi.norm(b, axis_name)
        tol_abs = jnp.maximum(tol * bnorm, jnp.asarray(0.0, b.dtype))

    def resid_of(x):
        with jax.named_scope("residual"):
            with jax.named_scope("operator"):
                ax = matvec(x)
            r = b - ax
            return r, arnoldi.norm(r, axis_name)

    r0, beta0 = resid_of(x0)
    # Bounded residual-history ring, chronological with inf padding on the
    # left; seeded with ||b - A x0|| so the trace starts at cycle 0.
    hist0 = jnp.full((history,), jnp.inf, beta0.dtype).at[-1].set(beta0)

    def cond(carry):
        _, _, beta, k, _, _ = carry
        return (beta > tol_abs) & (k < max_restarts)

    def body(carry):
        x, r, beta, k, steps, hist = carry
        if pipelined:
            x, inner = _gmres_cycle_pipelined(
                op_fn, update_fn, x, r, beta, m, tol_abs, precond,
                basis_dtype, axis_name)
        else:
            x, inner = _gmres_cycle(
                step_fn, x, r, beta, m, tol_abs, precond, basis_dtype
            )
        r, beta = resid_of(x)
        with jax.named_scope("residual"):
            hist = jnp.roll(hist, -1).at[-1].set(beta)
        return x, r, beta, k + 1, steps + inner, hist

    x, r, beta, k, steps, hist = lax.while_loop(
        cond, body,
        (x0, r0, beta0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
         hist0),
    )
    converged = beta <= tol_abs
    diags = Diagnostics(
        status=classify_residuals(hist, converged=converged),
        residual_history=hist * scale,
        history_len=jnp.minimum(k + 1, history).astype(jnp.int32),
    )
    return GmresResult(
        x=x * scale, residual=beta * scale, restarts=k, converged=converged,
        inner_steps=steps, done=converged | (k >= max_restarts),
        diagnostics=diags,
    )


# --------------------------------------------------------------------------
# Block multi-RHS solver
# --------------------------------------------------------------------------
# Schemes whose arithmetic is CGS2 — the batched block-GS kernel implements
# exactly that, so any of these may ride it in gmres_batched.
_CGS2_FAMILY = ("cgs2", "cgs2_fused", "fused", "arnoldi_fused",
                "cgs2_pipelined")


def _make_batched_gs(gs: str, m: int, n: int, basis_dtype) -> Callable:
    """Build ``batched_gs(v, w, j) -> ArnoldiStep`` (all args lane-batched).

    With a CGS2-family scheme, a kernel-capable backend and per-lane bases
    that fit VMEM, both Gram-Schmidt passes for every lane run through the
    batched block-GS kernel (kernels/block_gs.py): each grid step holds ONE
    lane's (m+1, n) basis resident, streaming it once per Arnoldi step
    instead of the vmapped reference's four.  Everything else — non-CGS2
    schemes, ``kernel_mode() == "ref"``, VMEM overflow — vmaps the jnp
    scheme (kernel scheme names degrade exactly as before).
    """
    if gs in _CGS2_FAMILY:
        from repro.kernels import tuning

        mode = tuning.kernel_mode()
        if mode != "ref" and tuning.block_gs_fits(m + 1, n, basis_dtype):
            from repro.kernels import block_gs

            interp = mode == "interpret"
            # The cycle allocates the lane bases pre-padded to the kernel's
            # tile grid (``basis_shape``, same pattern as the fused Arnoldi
            # path): padding the loop-carried (k, m+1, n) basis inside the
            # step would copy it through HBM every inner iteration.
            m1p, n_pad, _ = tuning.choose_block_gs(
                m + 1, n, 1, jnp.dtype(basis_dtype).name)

            def kernel_gs(v, w, j):
                mask = (jnp.arange(m1p)[None, :] <= j[:, None]).astype(
                    jnp.float32)
                w_pad = jnp.pad(w, ((0, 0), (0, n_pad - n)))  # (k, n_pad):
                h, w2 = block_gs.batched_cgs2(v, w_pad, mask,  # cheap next
                                              interpret=interp)  # to V
                return jax.vmap(arnoldi.finalize)(
                    w2[:, :n].astype(w.dtype), h[:, :m + 1].astype(w.dtype),
                    j)

            kernel_gs.basis_shape = (m1p, n_pad)
            return kernel_gs

    gs_step = arnoldi.step(_SCHEME_FALLBACK.get(gs, gs))
    return lambda v, w, j: jax.vmap(gs_step)(v, w, j)


def _block_cycle(blockmv, vprecond, batched_gs, x0, r0, beta, m, tol_abs,
                 active0, basis_dtype):
    """One restart cycle over k lanes stepping in lockstep.

    Lanes carry their own Krylov basis / Givens state / convergence latch;
    the ONE shared operand is A, which every step streams exactly once as a
    (n, k) block mat-vec.  Masking matches ``vmap(gmres)`` semantics: a
    done lane's Givens updates write identity columns and zeroed g entries,
    so the final per-lane triangular solve is unaffected.
    """
    k, n = x0.shape
    dtype = x0.dtype
    eps = jnp.asarray(jnp.finfo(dtype).tiny ** 0.5, dtype)

    # Kernel-backed GS may ask for tile-aligned lane bases (see
    # _make_batched_gs); padded rows/columns are zero and never touched.
    basis_rows, basis_cols = getattr(batched_gs, "basis_shape", (m + 1, n))
    with jax.named_scope("orthogonalize"):
        v0 = (r0 / jnp.maximum(beta, eps)[:, None]).astype(basis_dtype)
        v = jnp.zeros((k, basis_rows, basis_cols), basis_dtype).at[
            :, 0, :n].set(v0)
    with jax.named_scope("lsq"):
        giv = jax.vmap(lambda be: givens.init(m, be, dtype))(beta)
    done = jnp.logical_not(active0) | (beta <= tol_abs)
    steps = jnp.zeros((k,), jnp.int32)

    def cond(carry):
        _, _, done, steps = carry
        return jnp.any(jnp.logical_not(done) & (steps < m))

    def body(carry):
        v, giv, done, steps = carry
        j = steps                                     # per-lane step index
        active = jnp.logical_not(done) & (steps < m)
        # --- the k current Krylov vectors hit A as ONE GEMM ---
        with jax.named_scope("orthogonalize"):
            vj = jax.vmap(lambda vb, jj: vb[jj, :n])(v, j).astype(dtype)
        with jax.named_scope("precond"):
            z = vprecond(vj)
        with jax.named_scope("operator"):
            w = blockmv(z)                            # (k, n)
        with jax.named_scope("orthogonalize"):
            st = batched_gs(v, w, j)
            v_new = jax.vmap(lambda vb, vn, jj: vb.at[jj + 1, :n].set(vn))(
                v, st.v_next.astype(basis_dtype), j)
            v = jnp.where(active[:, None, None], v_new, v)
        with jax.named_scope("lsq"):
            giv = jax.vmap(
                lambda g, h, jj, act: givens.update(g, h, jj, active=act)
            )(giv, st.h.astype(dtype), j, active)
            resid = jax.vmap(givens.residual_norm)(giv, j)
            happy = st.h_last <= eps * 100.0
            done = done | (active & ((resid <= tol_abs) | happy))
            steps = steps + active.astype(jnp.int32)
        return v, giv, done, steps

    v, giv, done, steps = lax.while_loop(cond, body, (v, giv, done, steps))
    with jax.named_scope("lsq"):
        y = jax.vmap(givens.solve)(giv, steps)        # (k, m)
        dx = jnp.einsum("km,kmn->kn", y, v[:, :m, :n].astype(dtype))
    with jax.named_scope("precond"):
        dx = vprecond(dx)
    with jax.named_scope("lsq"):
        x = x0 + dx
    return x, steps


def _lane_norms(r: jax.Array) -> jax.Array:
    """Per-lane ``arnoldi.norm``: the same reduction ``gmres`` applies to
    one vector, so a lane's residuals round exactly like a standalone
    solve's (``jnp.linalg.norm(axis=1)`` differs in the last ulp)."""
    return jax.vmap(arnoldi.norm)(r)


def _block_matvec(op) -> Callable:
    """(k, n) -> (k, n) block mat-vec: ONE matrix stream for all k lanes.

    Explicit-storage operators accept an (n, k) operand natively, so the
    k current Krylov vectors hit the matrix as a single GEMM / block SpMV;
    matrix-free operators vmap (nothing to share).
    """
    if isinstance(op, EXPLICIT_OPERATORS):
        return lambda xs: op(xs.T).T
    return jax.vmap(op)


def check_precond(precond) -> None:
    """Reject non-callable ``precond`` EARLY with the argument named.

    A registry string or a stray object would otherwise surface as a
    TypeError deep inside a jitted cycle; every public solver calls this
    so the contract is uniform (honor it or raise a clear ValueError).
    Registry NAMES are a sharded-wrapper convenience only — they need an
    operator to build against (``make_preconditioner(name, op)``).
    """
    if precond is not None and not callable(precond):
        raise ValueError(
            f"precond must be callable (a Preconditioner instance or a "
            f"plain M^-1 apply fn), got {type(precond).__name__} "
            f"{precond!r}; to use a registry name, build it first: "
            f"preconditioners.make_preconditioner(name, op)")


def _batched_precond(precond) -> Callable:
    """(k, n) -> (k, n) lane-batched M^{-1} apply.

    ``Preconditioner`` instances expose ``batched`` (one shared operator
    stream for all lanes, e.g. the Chebyshev block recurrence); a plain
    callable vmaps; identity short-circuits to a passthrough so the
    unpreconditioned batched path is byte-identical to before.
    """
    check_precond(precond)
    if precond is None or getattr(precond, "is_identity", False):
        return lambda vs: vs
    batched = getattr(precond, "batched", None)
    if batched is not None:
        return batched
    return jax.vmap(precond)


@f32_matmuls
def gmres_batched_cycle(a, b: jax.Array, x: jax.Array, *, m: int = 30,
                        tol_abs=None, active=None, gs: str = "cgs2",
                        precond: Optional[Callable] = None,
                        compute_dtype=None):
    """ONE lockstep restart cycle over k lanes — the serving primitive.

    ``gmres_batched`` drives this same block cycle inside a while_loop
    until every lane is done; the solver server (``repro/serve``) instead
    calls it once per scheduler tick so converged lanes can be RETIRED at
    the restart boundary and refilled with queued requests — the
    decode-loop trick applied to Krylov lanes.  Lane contents are
    mathematically independent (the only shared operand is the one A
    stream of the block mat-vec), so a refilled lane's trajectory is
    exactly a standalone ``gmres`` solve of its system.

    Args:
      a: shared operator (anything ``gmres`` accepts).
      b: (k, n) per-lane right-hand sides (retired lanes may carry zeros).
      x: (k, n) current iterates (fresh lanes start at zero).
      m: restart length (static — part of the compiled cycle's identity).
      tol_abs: (k,) ABSOLUTE per-lane residual targets (callers own the
        tol * ||b|| scaling; zeros default, i.e. never converged).
      active: (k,) bool lane mask; inactive lanes pass through untouched
        and contribute only masked no-op arithmetic to the block GEMM.
      gs / precond / compute_dtype: as in ``gmres_batched``.

    Returns ``(x', beta', inner_steps)``: updated iterates, the TRUE
    per-lane residual norms ``||b - A x'||`` recomputed after the cycle
    (also fresh for just-refilled lanes — this is what retirement
    decisions read), and the per-lane Arnoldi steps taken.
    """
    op = as_operator(a)
    vprecond = _batched_precond(precond)
    basis_dtype = b.dtype if compute_dtype is None else compute_dtype
    batched_gs = _make_batched_gs(gs, m, b.shape[1], basis_dtype)
    blockmv = _block_matvec(op)
    if tol_abs is None:
        tol_abs = jnp.zeros(b.shape[:1], b.dtype)
    if active is None:
        active = jnp.ones(b.shape[:1], bool)

    def resid_of(x):
        with jax.named_scope("residual"):
            with jax.named_scope("operator"):
                ax = blockmv(x)
            r = b - ax
            return r, _lane_norms(r)

    r, beta = resid_of(x)
    act = active & (beta > tol_abs)
    x2, inner = _block_cycle(blockmv, vprecond, batched_gs, x, r, beta,
                             m, tol_abs, act, basis_dtype)
    with jax.named_scope("lsq"):
        x = jnp.where(act[:, None], x2, x)
    _, beta = resid_of(x)
    return x, beta, inner


@f32_matmuls
def gmres_batched(a, b: jax.Array, *, m: int = 30, tol=1e-5,
                  max_restarts=50, gs: str = "cgs2",
                  precond: Optional[Callable] = None,
                  compute_dtype=None) -> GmresResult:
    """Batch of right-hand sides, shape (batch, n), shared A — solved BLOCKED.

    Previously this was ``vmap(gmres)``: correct, but each lane's mat-vec
    stayed a GEMV, and on the kernel path a vmapped ``pallas_call`` re-streams
    A from HBM once PER LANE.  Now the k current Krylov vectors are stacked
    into an (n, k) block and hit A as a single GEMM per Arnoldi step — one
    shared HBM stream of A, a k-fold arithmetic-intensity win (this is the
    multi-RHS workload of the paper's Table 1 systems, batched).

    Orthogonalization is kernel-backed too: with a CGS2-family ``gs`` the
    per-lane Gram-Schmidt runs through the batched block-GS kernel
    (kernels/block_gs.py) — one grid step per lane with that lane's basis
    VMEM-resident, cutting its per-step HBM streams from four to one, the
    same way ``block_matvec`` already cut the A streams.  Lanes whose
    bases exceed VMEM (``tuning.block_gs_fits``), non-CGS2 schemes, and
    kernel-free backends vmap the jnp scheme instead.  Per-lane Givens
    state stays lane-parallel via vmap (O(m^2) scalar work, not worth a
    kernel).  Matrix-free operators fall back to a vmapped mat-vec
    (nothing to share).

    Any explicit-storage operator (``DenseOperator``, ``SparseOperator``,
    ``BandedOperator``) rides the block path: their ``__call__`` accepts an
    (n, k) operand natively, so one stream of the matrix (dense tiles, ELL
    values/cols, or stencil bands) feeds all k lanes.

    PER-LANE stopping: ``tol`` and ``max_restarts`` may be scalars (every
    lane alike) or (batch,)-shaped arrays — heterogeneous solves packed
    into one block.  Each lane latches its own convergence against its own
    ``tol * ||b_lane||`` target and its own restart budget; a lane that
    exhausts its budget is retired as FAILED (``done`` True, ``converged``
    False) WITHOUT stalling the cohort — the remaining lanes keep cycling
    and the failed lane rides along as masked no-ops.  The serving layer
    (``repro/serve``) goes one step further and swaps retired lanes for
    queued requests between cycles via ``gmres_batched_cycle``.
    """
    op = as_operator(a)
    vprecond = _batched_precond(precond)
    basis_dtype = b.dtype if compute_dtype is None else compute_dtype
    batched_gs = _make_batched_gs(gs, m, b.shape[1], basis_dtype)
    blockmv = _block_matvec(op)

    bnorm = _lane_norms(b)
    # tol / max_restarts broadcast: scalar or per-lane (batch,) arrays.
    tol_abs = jnp.maximum(jnp.asarray(tol, b.dtype) * bnorm,
                          jnp.asarray(0.0, b.dtype))
    max_restarts = jnp.asarray(max_restarts, jnp.int32)

    def resid_of(x):
        with jax.named_scope("residual"):
            with jax.named_scope("operator"):
                ax = blockmv(x)
            r = b - ax
            return r, _lane_norms(r)

    x0 = jnp.zeros_like(b)
    r0, beta0 = resid_of(x0)
    k0 = jnp.zeros(b.shape[:1], jnp.int32)

    def cond(carry):
        _, _, beta, kk, _ = carry
        return jnp.any((beta > tol_abs) & (kk < max_restarts))

    def body(carry):
        x, r, beta, kk, steps = carry
        active = (beta > tol_abs) & (kk < max_restarts)
        x2, inner = _block_cycle(blockmv, vprecond, batched_gs, x, r, beta,
                                 m, tol_abs, active, basis_dtype)
        with jax.named_scope("lsq"):
            x = jnp.where(active[:, None], x2, x)
        r, beta = resid_of(x)
        return x, r, beta, kk + active.astype(jnp.int32), steps + inner

    x, r, beta, kk, steps = lax.while_loop(
        cond, body, (x0, r0, beta0, k0, jnp.zeros(b.shape[:1], jnp.int32))
    )
    converged = beta <= tol_abs
    return GmresResult(x=x, residual=beta, restarts=kk, converged=converged,
                       inner_steps=steps,
                       done=converged | (kk >= max_restarts))


@functools.partial(jax.jit,
                   static_argnames=("m", "tol", "max_restarts", "gs",
                                    "compute_dtype"))
def gmres_jit(a, b, *, m=30, tol=1e-5, max_restarts=50, gs="cgs2",
              compute_dtype=None):
    """Convenience fully-jit'd solve (the paper's device-resident strategy).

    Same arguments and semantics as ``gmres`` (which see), with the
    jit-static knobs (``m``, ``tol``, ``gs``, ``compute_dtype``, ...)
    declared so repeated solves at one configuration reuse the compiled
    program.  ``a`` may be any operator ``gmres`` accepts — operators are
    pytrees, so new array payloads do NOT retrace.
    """
    return gmres(a, b, m=m, tol=tol, max_restarts=max_restarts, gs=gs,
                 compute_dtype=compute_dtype)
