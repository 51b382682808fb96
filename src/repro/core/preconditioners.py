"""The preconditioning subsystem: right preconditioners for every solver path.

The paper runs unpreconditioned GMRES (pracma's default).  On a pod, a good
preconditioner is the cheapest way to cut collective rounds: fewer Arnoldi
steps = fewer all-gathers — it deletes steps where every other layer of this
repo merely accelerates one.

Every member implements the ``Preconditioner`` protocol:

  apply      ``pc(v) -> M^{-1} v`` — a jit/vmap-compatible callable.  Setup
             (factorizations, spectral-interval estimation) happens ONCE at
             construction, eagerly, and is closed over.
  batched    ``pc.batched(vs)`` — the (k, n) multi-lane form the blocked
             solver paths use (``gmres_batched`` / the serve layer); the
             default vmaps ``apply``, members with a cheaper vectorized form
             override it.
  cost       ``pc.cost(op)`` -> ``PrecondCost`` — modeled setup/apply flops
             and HBM bytes plus ``matvec_equiv``, the apply cost in units of
             one operator mat-vec.  This is what the strategies table and
             the ``precond_*`` bench rows report: a preconditioner pays off
             when (steps cut) x (step cost) > matvec_equiv x (steps left).
  shard      ``pc.shard_aware`` + ``pc.rebind(op_local)`` — shard-aware
             members rebuild themselves INSIDE the distributed wrapper's
             shard_map body from the local operator shard (banded
             block-Jacobi masks the bands to the local diagonal block;
             Chebyshev re-targets the halo-exchange mat-vec).  Members with
             ``shard_aware=False`` make ``gmres_sharded`` raise instead of
             silently producing a wrong-layout apply.
  identity   ``pc.n`` / ``pc.requires_fmt`` — admission metadata the serve
             layer validates against the handle's operator BEFORE a request
             can reach a lane (``serve.request.validate_precond``).

Members
-------
  identity       no-op (``is_identity=True`` keeps the fused-Arnoldi path).
  jacobi         diagonal scaling, every format, shard-aware.
  block_jacobi   dense block-diagonal LU (batched level-3 apply).
  neumann        truncated Neumann series — mat-vec chain, shard-aware.
  chebyshev      degree-``order`` Chebyshev polynomial for spectra inside
                 ``[lam_min, lam_max]`` (interval auto-estimated via
                 Gershgorin + power iteration, ``estimate_interval``).  On
                 single-shard banded operators the whole recurrence runs
                 FUSED in one matrix-powers-style pallas_call — the band
                 stack is streamed from HBM once for all ``order`` mat-vecs
                 (``kernels/matrix_powers.banded_cheb_apply``).
  banded_ilu0    ILU(0) on the band pattern of a ``BandedOperator`` —
                 O(n * nbands^2) one-pass setup, applied as two banded
                 triangular sweeps (``kernels/trisolve``).  ``line_jacobi``
                 is the same member restricted to the (-1, 0, +1) bands,
                 where ILU(0) is the EXACT tridiagonal factorization.
  banded_block_jacobi  the shard-local composition: each shard drops the
                 band entries that cross its row range and ILU(0)-factors
                 its own diagonal block — ZERO preconditioner communication,
                 composing with the halo-exchange mat-vec path.

Polynomial preconditioning is the TPU-sweet-spot choice: it replaces
latency-bound inner products with MXU-bound extra mat-vecs.  The banded
sweeps are the opposite trade (latency-bound, but ~1 mat-vec equivalent
per apply and strong on stencils); the cost model makes the choice legible.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class PrecondCost:
    """Modeled cost account (floats — structural, not measured)."""
    setup_flops: float          # one-time construction cost
    apply_flops: float          # per apply(v)
    apply_hbm_bytes: float      # per apply(v), modeled operand traffic
    matvec_equiv: float         # apply cost in units of one op mat-vec


def _op_nnz(op) -> float:
    """Structural nonzeros of an explicit operator (dense counts all)."""
    from repro.core import operators as op_mod
    if isinstance(op, op_mod.BandedOperator):
        return float(op.bands.shape[0] * op.bands.shape[1])
    if isinstance(op, op_mod.SparseOperator):
        return float(op.values.shape[0] * op.values.shape[1])
    if isinstance(op, op_mod.SlicedEllOperator):
        return float(op.storage_entries)
    if isinstance(op, op_mod.DenseOperator):
        return float(op.a.shape[0] * op.a.shape[1])
    n = _op_dim(op) or 0
    return float(n) * 8.0       # matrix-free: stencil-like guess


def _op_dim(op):
    """Row dimension of an operator (None when it cannot be told)."""
    shape = getattr(op, "shape", None)
    if shape is not None and len(shape):
        return int(shape[0])
    n = getattr(op, "n", None)
    return int(n) if n else None


class Preconditioner:
    """Base protocol: a callable ``v -> M^{-1} v`` with metadata.

    Subclasses set ``name``/``shard_aware``/``requires_fmt`` and implement
    ``__call__`` (single-vector apply) and ``cost``.  ``n`` is the operator
    dimension the apply is bound to (``None`` = shape-agnostic).
    """

    name: str = "preconditioner"
    shard_aware: bool = False
    is_identity: bool = False
    requires_fmt: Optional[str] = None   # "dense" | "banded" | None (any)
    n: Optional[int] = None

    def __call__(self, v: jax.Array) -> jax.Array:
        raise NotImplementedError

    def batched(self, vs: jax.Array) -> jax.Array:
        """(k, n) -> (k, n) multi-lane apply; default vmaps the single form."""
        return jax.vmap(self.__call__)(vs)

    def rebind(self, op_local) -> "Preconditioner":
        """Rebuild against a LOCAL operator shard (inside shard_map).

        Only meaningful when ``shard_aware``; the distributed wrappers call
        it per shard so setup happens in local coordinates.
        """
        raise ValueError(
            f"preconditioner {self.name!r} is not shard-aware; "
            f"gmres_sharded supports identity/jacobi/chebyshev/"
            f"banded_block_jacobi (or the 'block_jacobi' dense string)")

    def cost(self) -> PrecondCost:
        return PrecondCost(0.0, 0.0, 0.0, 0.0)

    def __repr__(self):
        nn = "" if self.n is None else f", n={self.n}"
        return f"<{type(self).__name__} {self.name}{nn}>"


class IdentityPreconditioner(Preconditioner):
    name = "identity"
    shard_aware = True
    is_identity = True

    def __call__(self, v):
        return v

    def batched(self, vs):
        return vs

    def rebind(self, op_local):
        return self


def _diag_of(op) -> jax.Array:
    """Main diagonal of an explicit operator, any storage format."""
    from repro.core import operators as op_mod
    if isinstance(op, op_mod.DenseOperator):
        return jnp.diagonal(op.a)
    if isinstance(op, op_mod.BandedOperator):
        if 0 not in op.offsets:
            raise ValueError("jacobi needs the main diagonal; this banded "
                             "operator has no offset-0 band")
        return op.bands[op.offsets.index(0)]
    if isinstance(op, op_mod.SparseOperator):
        n = op.values.shape[0]
        hit = op.cols == jnp.arange(n)[:, None]
        return jnp.sum(jnp.where(hit, op.values, 0), axis=1)
    if isinstance(op, op_mod.SlicedEllOperator):
        # Per bin, a row's diagonal hit is where a stored GLOBAL column
        # equals the row's ORIGINAL index; scatter the sorted-frame result
        # back through perm.  (Padding slots: value 0, so a spurious
        # col-0 match on original row 0 adds exactly 0.)
        return _sell_rowreduce(
            op, lambda vals, cols, orig:
                jnp.sum(jnp.where(cols == orig[:, None], vals, 0), axis=1))
    raise ValueError(f"jacobi needs explicit storage to read diag(A); got "
                     f"{type(op).__name__}")


def _sell_rowreduce(op, fn) -> jax.Array:
    """Apply ``fn(vals, cols, orig_rows) -> (rows_b,)`` per sliced-ELL bin
    and scatter the concatenated result back to original row order."""
    parts, start = [], 0
    for vals, cols in zip(op.bin_values, op.bin_cols):
        rb = vals.shape[0]
        parts.append(fn(vals, cols, op.perm[start:start + rb]))
        start += rb
    out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    if op.identity_perm:
        return out
    return jnp.zeros_like(out).at[op.perm].set(out)


class JacobiPreconditioner(Preconditioner):
    """Diagonal scaling M = diag(A) — every format, shard-aware for free
    (the diagonal is row-sharded exactly like v)."""

    name = "jacobi"
    shard_aware = True

    def __init__(self, a):
        from repro.core.operators import as_operator
        op = as_operator(a)
        d = _diag_of(op)
        guard = jnp.asarray(jnp.finfo(d.dtype).tiny ** 0.5, d.dtype)
        mag = jnp.maximum(jnp.abs(d), guard)
        self.inv_d = jnp.sign(jnp.where(d == 0, 1, d)) / mag
        self.n = int(d.shape[0])

    def __call__(self, v):
        return self.inv_d * v

    def batched(self, vs):
        return self.inv_d[None, :] * vs

    def rebind(self, op_local):
        # Under shard_map the local operator's storage IS the local rows,
        # so setup in local coordinates is just construction again — except
        # dense, whose local block is (rows, n); slice the diagonal block.
        from repro.core import operators as op_mod
        from repro.kernels import tuning
        if isinstance(op_local, op_mod.DenseOperator) and (
                op_local.a.shape[0] != op_local.a.shape[1]):
            # Dense shards are (rows, n): the local diagonal entries live
            # in the shard's own diagonal block.
            rows = op_local.a.shape[0]
            p = lax.axis_index(tuning.shard_axis())
            block = lax.dynamic_slice(op_local.a, (0, p * rows),
                                      (rows, rows))
            return JacobiPreconditioner(block)
        return JacobiPreconditioner(op_local)

    def cost(self):
        return PrecondCost(setup_flops=float(self.n or 0),
                           apply_flops=float(self.n or 0),
                           apply_hbm_bytes=12.0 * float(self.n or 0),
                           matvec_equiv=0.1)


class BlockJacobiPreconditioner(Preconditioner):
    """Dense block-diagonal M: invert ``block``-sized diagonal blocks.

    n must be divisible by ``block``; blocks are factorized once (host-side
    cost amortized across the solve) and applied as a batched triangular
    solve pair — a batched level-3 op, MXU-friendly.  Dense single-shard
    only; the sharded dense equivalent is ``gmres_sharded``'s shard-local
    ``precond="block_jacobi"`` and the stencil equivalent is
    ``banded_block_jacobi``.
    """

    name = "block_jacobi"
    requires_fmt = "dense"

    def __init__(self, a: jax.Array, block: int):
        from repro.core import operators as op_mod
        if isinstance(a, op_mod.DenseOperator):
            a = a.a
        n = a.shape[0]
        assert n % block == 0, (n, block)
        nb = n // block
        blocks = jnp.stack([
            a[i * block:(i + 1) * block, i * block:(i + 1) * block]
            for i in range(nb)])
        self.lu, self.piv = jax.vmap(jax.scipy.linalg.lu_factor)(blocks)
        self.n = int(n)
        self.block = int(block)

    def __call__(self, v):
        nb = self.n // self.block
        vb = v.reshape(nb, self.block)
        out = jax.vmap(jax.scipy.linalg.lu_solve)((self.lu, self.piv), vb)
        return out.reshape(self.n)

    def cost(self):
        b = float(self.block)
        n = float(self.n)
        return PrecondCost(setup_flops=n * b * b * (2.0 / 3.0),
                           apply_flops=2.0 * n * b,
                           apply_hbm_bytes=4.0 * (n * b + 2 * n),
                           matvec_equiv=b / n)


class NeumannPreconditioner(Preconditioner):
    """Truncated Neumann series M^{-1} ~= sum_k (I - w D^{-1} A)^k w D^{-1}.

    Pure mat-vec chain — converts preconditioning work into level-2/3 ops
    with zero extra collectives beyond the mat-vecs themselves.
    """

    name = "neumann"
    shard_aware = True

    def __init__(self, a, *, order: int = 2, omega: float | None = None):
        from repro.core.operators import as_operator
        self.op = as_operator(a)
        self.inv_d = JacobiPreconditioner(self.op).inv_d
        self.order = int(order)
        self.omega = 1.0 if omega is None else float(omega)
        self.n = int(self.inv_d.shape[0])

    def __call__(self, v):
        z = self.omega * self.inv_d * v
        acc = z
        for _ in range(self.order):
            z = z - self.omega * self.inv_d * self.op(z)
            acc = acc + z
        return acc

    def rebind(self, op_local):
        pc = object.__new__(NeumannPreconditioner)
        pc.op = op_local
        pc.inv_d = JacobiPreconditioner(op_local).inv_d
        pc.order = self.order
        pc.omega = self.omega
        pc.n = self.n
        return pc

    def cost(self):
        nnz = _op_nnz(self.op)
        return PrecondCost(setup_flops=float(self.n),
                           apply_flops=self.order * 2.0 * nnz,
                           apply_hbm_bytes=self.order * 4.0 * nnz,
                           matvec_equiv=float(self.order))


# --------------------------------------------------------------------------
# Spectral-interval estimation (Chebyshev setup)
# --------------------------------------------------------------------------
def _row_sums_and_diag(op) -> Tuple[jax.Array, jax.Array]:
    """(sum_j |a_ij|, a_ii) per row for any explicit operator."""
    from repro.core import operators as op_mod
    if isinstance(op, op_mod.BandedOperator):
        nbands, n = op.bands.shape
        i = jnp.arange(n)
        sums = jnp.zeros((n,), jnp.float32)
        for d, off in enumerate(op.offsets):
            valid = (i + off >= 0) & (i + off < n)
            sums = sums + jnp.where(valid,
                                    jnp.abs(op.bands[d].astype(jnp.float32)),
                                    0.0)
        return sums, _diag_of(op).astype(jnp.float32)
    if isinstance(op, op_mod.SparseOperator):
        return (jnp.sum(jnp.abs(op.values.astype(jnp.float32)), axis=1),
                _diag_of(op).astype(jnp.float32))
    if isinstance(op, op_mod.SlicedEllOperator):
        sums = _sell_rowreduce(
            op, lambda vals, cols, orig:
                jnp.sum(jnp.abs(vals.astype(jnp.float32)), axis=1))
        return sums, _diag_of(op).astype(jnp.float32)
    if isinstance(op, op_mod.DenseOperator):
        a = op.a.astype(jnp.float32)
        return jnp.sum(jnp.abs(a), axis=1), jnp.diagonal(a)
    raise ValueError(f"spectral bounds need explicit storage; got "
                     f"{type(op).__name__}")


def spectral_bounds(op) -> Tuple[jax.Array, jax.Array]:
    """Traced Gershgorin bounds (lam_lo, lam_hi) — usable under jit.

    ``lam_lo`` may be <= 0 for non-strictly-dominant systems (2-D Poisson
    touches 0 at the boundary rows); callers clamp with a relative floor.
    """
    sums, diag = _row_sums_and_diag(op)
    radius = sums - jnp.abs(diag)
    return jnp.min(diag - radius), jnp.max(diag + radius)


def estimate_interval(a, *, iters: int = 8, floor: float = 1.0 / 30.0,
                      slack: float = 3.0) -> Tuple[float, float]:
    """Cheap eager spectral-interval estimate for Chebyshev setup.

    ``lam_max`` must BOUND the spectrum from above: the Chebyshev
    polynomial oscillates inside [lam_min, lam_max] but grows without
    sign control beyond lam_max, so any eigenvalue above it can flip the
    preconditioned operator indefinite and STALL the outer solve (an
    overestimate merely costs a little polynomial efficiency — the risk is
    one-sided).  Gershgorin IS such a bound and is tight for the
    diagonally-dominant stencils this preconditioner targets, so it wins
    by default; a few power iterations supply a Rayleigh estimate of the
    spectral radius, used only to detect a PATHOLOGICALLY loose Gershgorin
    bound (> ``slack`` x Rayleigh — e.g. one extreme outlier row), where
    we fall back to ``slack/2 x`` the measured radius instead.

    ``lam_min``: the Gershgorin lower bound clamped to ``floor *
    lam_max`` — stencil spectra reach ~0 and Chebyshev on
    [lam_max/30, lam_max] remains an excellent smoother-style
    preconditioner (modes below lam_min stay positive, just less damped;
    GMRES mops them up).

    Everything is a RATIO of A's entries, so the estimate scales linearly
    with A and preconditioned solves stay scale-invariant (the PR 3
    contract).  Eager (returns Python floats); under an enclosing jit
    trace the whole estimate runs at COMPILE time against the operator's
    concrete storage (``ensure_compile_time_eval``) — the interval is
    static metadata that parameterizes the compiled recurrence, never a
    traced value.
    """
    from repro.core.operators import as_operator
    op = as_operator(a)
    with jax.ensure_compile_time_eval():
        lam_lo, lam_hi = spectral_bounds(op)
        gersh_max = float(lam_hi)
        n = int(_row_sums_and_diag(op)[0].shape[0])
        # Deterministic, spread-spectrum probe (no PRNG: setup must be
        # cheap and reproducible; the cosine ramp overlaps every smooth
        # mode).
        v = jnp.cos(jnp.arange(n, dtype=jnp.float32) * 0.7) + 0.5
        v = v / jnp.linalg.norm(v)
        rayleigh = gersh_max
        for _ in range(max(iters, 1)):
            w = op(v.astype(op_dtype(op))).astype(jnp.float32)
            rayleigh = float(jnp.vdot(v, w))
            nrm = float(jnp.linalg.norm(w))
            if nrm <= 0.0:
                break
            v = w / nrm
    lam_max = gersh_max
    if abs(rayleigh) > 0.0 and gersh_max > slack * abs(rayleigh):
        lam_max = (slack / 2.0) * abs(rayleigh)
    if lam_max <= 0.0:
        lam_max = max(gersh_max, 1.0)
    lam_min = max(float(lam_lo), floor * lam_max)
    return lam_min, lam_max


def op_dtype(op):
    from repro.core import operators as op_mod
    if isinstance(op, op_mod.BandedOperator):
        return op.bands.dtype
    if isinstance(op, op_mod.SparseOperator):
        return op.values.dtype
    if isinstance(op, op_mod.DenseOperator):
        return op.a.dtype
    return jnp.float32


def cheb_coeffs(order: int, lam_min: float, lam_max: float
                ) -> Tuple[float, float, Tuple[float, ...]]:
    """Static scalars of the degree-``order`` Chebyshev recurrence.

    Returns (theta, delta, rhos): the interval center/half-width and the
    ``order - 1`` rho values of the classic three-term iteration — all
    Python floats, so kernel implementations can bake them in statically.
    """
    theta = 0.5 * (lam_max + lam_min)
    delta = max(0.5 * (lam_max - lam_min), 1e-12 * abs(theta) or 1e-30)
    sigma1 = theta / delta
    rhos = []
    rho_old = 1.0 / sigma1
    for _ in range(order - 1):
        rho = 1.0 / (2.0 * sigma1 - rho_old)
        rhos.append((rho, rho_old))
        rho_old = rho
    return theta, delta, tuple(rhos)


class ChebyshevPreconditioner(Preconditioner):
    """Chebyshev polynomial preconditioner for spectra in [lam_min, lam_max].

    Classic three-term recurrence; like Neumann, trades inner products for
    mat-vecs, but with the optimal polynomial for a known spectral interval
    (auto-estimated when not given — ``estimate_interval``).

    Dispatch: on a single-shard ``BandedOperator`` with a kernel-capable
    backend the WHOLE recurrence is one fused pallas_call — the band stack
    is read from HBM once for all ``order`` mat-vecs, mirroring the
    matrix-powers kernel's one-pass contract
    (``kernels/matrix_powers.banded_cheb_apply``, gated by
    ``tuning.cheb_fits``).  Everywhere else (dense/ELL/matrix-free, the
    multi-lane ``batched`` form, rebound shards) the recurrence runs
    through the operator's own mat-vec — which under a shard_context is the
    halo-exchange path, so the sharded apply costs ``order`` ppermutes and
    ZERO psums (the interval is static; nothing else reduces).
    """

    name = "chebyshev"
    shard_aware = True

    def __init__(self, a, *, order: int = 4,
                 lam_min: Optional[float] = None,
                 lam_max: Optional[float] = None):
        from repro.core.operators import as_operator
        self.op = as_operator(a)
        if lam_min is None or lam_max is None:
            lam_min, lam_max = estimate_interval(self.op)
        self.order = int(order)
        self.lam_min = float(lam_min)
        self.lam_max = float(lam_max)
        self.theta, self.delta, self.rhos = cheb_coeffs(
            self.order, self.lam_min, self.lam_max)
        self.n = _op_dim(self.op)

    # -- plain (psum-safe, format-agnostic) recurrence ---------------------
    def _apply_ref(self, v, matvec):
        theta, delta = self.theta, self.delta
        z = v / theta
        z_old = jnp.zeros_like(v)
        for rho, rho_old in self.rhos:
            z_new = (rho * (2.0 / delta * (v - matvec(z))
                            + rho_old * (z - z_old)) + z)
            z_old, z = z, z_new
        return z

    def __call__(self, v):
        from repro.core import operators as op_mod
        from repro.kernels import matrix_powers, tuning
        op = self.op
        mode = tuning.kernel_mode()
        if (mode != "ref" and tuning.shard_axis() is None
                and isinstance(op, op_mod.BandedOperator)
                and v.ndim == 1):
            halo = max(abs(int(o)) for o in op.offsets)
            if tuning.cheb_fits(v.shape[0], op.bands.shape[0],
                                op.bands.dtype, halo=halo):
                return matrix_powers.banded_cheb_apply(
                    op.bands, v, op.offsets, theta=self.theta,
                    delta=self.delta, rhos=self.rhos,
                    interpret=mode == "interpret")
        return self._apply_ref(v, op)

    def batched(self, vs):
        # One shared operator stream per recurrence step: the (k, n) block
        # hits A through the same block mat-vec the batched solver uses.
        from repro.core.gmres import _block_matvec
        blockmv = _block_matvec(self.op)
        return self._apply_ref(vs, blockmv)

    def rebind(self, op_local):
        pc = object.__new__(ChebyshevPreconditioner)
        pc.op = op_local
        pc.order = self.order
        pc.lam_min, pc.lam_max = self.lam_min, self.lam_max
        pc.theta, pc.delta, pc.rhos = self.theta, self.delta, self.rhos
        pc.n = self.n
        return pc

    def cost(self):
        nnz = _op_nnz(self.op)
        matvecs = float(self.order)
        return PrecondCost(
            setup_flops=10.0 * nnz,                  # interval estimation
            apply_flops=matvecs * 2.0 * nnz + matvecs * 6.0 * float(self.n or 0),
            # fused banded path streams the band stack ONCE for all
            # `order` mat-vecs; vectors stay VMEM-resident.
            apply_hbm_bytes=4.0 * (nnz + 2.0 * float(self.n or 0)),
            matvec_equiv=matvecs)


class BandedILU0Preconditioner(Preconditioner):
    """ILU(0) on the band pattern of a ``BandedOperator``.

    Setup is ONE pass over the rows (``kernels/trisolve.banded_ilu0``,
    a lax.scan carrying the last ``halo`` factored rows — O(n * nbands^2)
    flops, O(bands) live state).  Apply is two banded triangular sweeps
    (unit-lower forward, upper backward) through the
    ``kernels/trisolve.banded_trisweep`` kernel on the standard
    compiled/interpret/ref dispatch (``trisolve`` decides from the
    offsets and the vector; ``tuning.trisweep_fits`` is its VMEM gate).

    ``pattern`` restricts the factorization to a subset of the operator's
    offsets: ``pattern=(-1, 0, 1)`` is LINE-JACOBI — ILU(0) of the
    tridiagonal part, which is its EXACT factorization — see
    ``line_jacobi``.  Not shard-aware (the sweeps recur across the whole
    row range); the sharded composition is ``banded_block_jacobi``.
    """

    name = "banded_ilu0"
    requires_fmt = "banded"

    def __init__(self, op, *, pattern: Optional[Tuple[int, ...]] = None):
        from repro.core import operators as op_mod
        from repro.kernels import trisolve
        if not isinstance(op, op_mod.BandedOperator):
            raise ValueError(
                f"banded_ilu0 needs a BandedOperator (its setup walks the "
                f"band pattern); got {type(op).__name__} — use jacobi/"
                f"chebyshev for dense or ELL operators")
        self.op = op
        bands, offsets = op.bands, tuple(int(o) for o in op.offsets)
        if pattern is not None:
            keep = [d for d, off in enumerate(offsets) if off in pattern]
            if not any(offsets[d] == 0 for d in keep):
                raise ValueError("ilu0 pattern must include the diagonal")
            bands = bands[jnp.asarray(keep)]
            offsets = tuple(offsets[d] for d in keep)
        self.pattern = pattern
        (self.l_bands, self.l_offsets,
         self.u_bands, self.u_offsets) = trisolve.banded_ilu0(bands, offsets)
        # The sweep kernel's lane-dense copies of the factors (None where
        # it cannot take them), built here so no apply re-lays the bands.
        self.l_tiles = trisolve.kernel_tiles(self.l_bands, self.l_offsets,
                                             unit_diag=True)
        self.u_tiles = trisolve.kernel_tiles(self.u_bands, self.u_offsets,
                                             unit_diag=False)
        self.n = int(bands.shape[1])

    def _sweeps(self, v):
        from repro.kernels import trisolve
        z = trisolve.banded_trisweep(self.l_bands, v, self.l_offsets,
                                     unit_diag=True, lower=True,
                                     tiles=self.l_tiles)
        return trisolve.banded_trisweep(self.u_bands, z, self.u_offsets,
                                        unit_diag=False, lower=False,
                                        tiles=self.u_tiles)

    def __call__(self, v):
        return self._sweeps(v)

    def batched(self, vs):
        # The jnp reference sweeps vectorize over lanes directly.
        from repro.kernels import trisolve
        sweep = jax.vmap(lambda v: trisolve.banded_trisweep_ref(
            self.l_bands, v, self.l_offsets, unit_diag=True, lower=True))
        back = jax.vmap(lambda v: trisolve.banded_trisweep_ref(
            self.u_bands, v, self.u_offsets, unit_diag=False, lower=False))
        return back(sweep(vs))

    def cost(self):
        nbands = float(self.l_bands.shape[0] + self.u_bands.shape[0])
        n = float(self.n)
        nnz = max(_op_nnz(self.op), 1.0)
        return PrecondCost(setup_flops=n * nbands * nbands,
                           apply_flops=2.0 * n * nbands,
                           apply_hbm_bytes=4.0 * (n * nbands + 3.0 * n),
                           matvec_equiv=(n * nbands) / nnz)


class BandedBlockJacobiPreconditioner(BandedILU0Preconditioner):
    """Shard-local banded block-Jacobi: ILU(0) of each shard's own block.

    Single-shard it IS ``banded_ilu0``.  Rebinding inside the distributed
    wrapper's shard_map body masks the band entries whose column index
    leaves the local row range (in local coordinates: ``i + off`` outside
    ``[0, n_local)`` — identical on every shard, so no shard-id dependence)
    and factors the remaining LOCAL diagonal block.  The apply is then
    shard-local with ZERO communication, composing with the halo-exchange
    mat-vec exactly as the dense ``_local_block_jacobi`` composes with the
    all-gather one.
    """

    name = "banded_block_jacobi"
    shard_aware = True

    def rebind(self, op_local):
        # Bands arrive row-sharded: op_local.bands is the (nbands, n_local)
        # slice.  BandedOperator's banded storage already zeroes nothing —
        # out-of-range reads are zero via the matvec's halo — so the mask
        # below is what truncates couplings to the local block.
        return BandedBlockJacobiPreconditioner(op_local,
                                               pattern=self.pattern)


def make_preconditioner(name: str, op, **kw) -> Preconditioner:
    """Factory by registry name (see ``PRECONDITIONERS``)."""
    try:
        factory = PRECONDITIONERS[name]
    except KeyError:
        raise ValueError(f"unknown preconditioner {name!r}; options: "
                         f"{sorted(PRECONDITIONERS)}") from None
    return factory(op, **kw)


# --------------------------------------------------------------------------
# Callable-style factories (the original module API, kept stable — each now
# returns a Preconditioner instance, which is still a plain callable).
# --------------------------------------------------------------------------
def identity() -> Preconditioner:
    return IdentityPreconditioner()


def jacobi(a) -> Preconditioner:
    """Diagonal scaling M = diag(A)."""
    return JacobiPreconditioner(a)


def block_jacobi(a, block: int) -> Preconditioner:
    return BlockJacobiPreconditioner(a, block)


def neumann(a, *, order: int = 2,
            omega: float | None = None) -> Preconditioner:
    return NeumannPreconditioner(a, order=order, omega=omega)


def chebyshev(a, *, order: int = 4, lam_min: Optional[float] = None,
              lam_max: Optional[float] = None) -> Preconditioner:
    return ChebyshevPreconditioner(a, order=order, lam_min=lam_min,
                                   lam_max=lam_max)


def banded_ilu0(op) -> Preconditioner:
    return BandedILU0Preconditioner(op)


def line_jacobi(op) -> Preconditioner:
    """ILU(0) restricted to the (-1, 0, +1) bands — exact tridiagonal
    (Thomas) factorization of the operator's line coupling."""
    return BandedILU0Preconditioner(op, pattern=(-1, 0, 1))


def banded_block_jacobi(op) -> Preconditioner:
    return BandedBlockJacobiPreconditioner(op)


PRECONDITIONERS = {
    "none": lambda a, **kw: identity(),
    "jacobi": lambda a, **kw: jacobi(a),
    "block_jacobi": lambda a, block=64, **kw: block_jacobi(a, block),
    "neumann": lambda a, order=2, **kw: neumann(a, order=order),
    "chebyshev": lambda a, order=4, lam_min=None, lam_max=None, **kw:
        chebyshev(a, order=order, lam_min=lam_min, lam_max=lam_max),
    "banded_ilu0": lambda a, **kw: banded_ilu0(a),
    "line_jacobi": lambda a, **kw: line_jacobi(a),
    "banded_block_jacobi": lambda a, **kw: banded_block_jacobi(a),
}
