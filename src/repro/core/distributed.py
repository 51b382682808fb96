"""Distributed GMRES: the paper's device-memory wall, removed by sharding.

The paper could not exceed N = 10000 because A (N^2 doubles) had to fit a
2 GB card.  Here the operator is **row-sharded** across a mesh axis: chip
p owns row block p of the matrix storage (dense rows, ELL rows, or band
columns) and the matching shard of every Krylov vector.  Per Arnoldi step
the communication is:

  - the operand exchange for the mat-vec — an all-gather (n values) for
    dense A, or a ``halo_exchange`` of O(halo) boundary values for
    banded/ELL operators (the Ioannidis et al. 1906.04051 picture);
  - psum-completed inner products — 2 rounds for CGS2, j rounds for MGS —

which is exactly why CGS2 is the distributed scheme of choice, and why
the s-step solver (one exchange + one psum per s steps on banded systems)
is the communication-avoiding end of the same line.

There is ONE cycle implementation.  Everything here is a thin
``shard_map`` wrapper: the body enters ``kernels.tuning.shard_context``
(so operators and schemes dispatch their per-shard kernel variants — the
split-phase CGS2 pair, halo SpMV, CA matrix powers) and calls the very
same ``gmres`` / ``gmres_sstep`` the single-device solve uses,
parameterized by ``axis_name``.  No Arnoldi loop, no Givens rotation, no
orthogonalization scheme lives in this file — distribution is a
deployment config, not a fork of the numerics.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import operators as op_mod
from repro.core.gmres import Diagnostics, gmres, GmresResult
from repro.core.sstep import gmres_sstep
from repro.kernels import tuning


def shard_specs(op, axis: str):
    """Row-sharding PartitionSpec pytree for an explicit operator.

    The returned object mirrors the operator's pytree structure with a
    spec at every array leaf — exactly what ``shard_map``'s ``in_specs``
    (and, via ``NamedSharding``, ``jax.jit``'s ``in_shardings``) want:

      DenseOperator   a      -> P(axis, None)     row blocks
      SparseOperator  values -> P(axis, None)     row blocks (cols too;
                                column indices stay GLOBAL — the sharded
                                ``__call__`` remaps them per shard)
      BandedOperator  bands  -> P(None, axis)     column blocks of the
                                band stack == row blocks of the matrix
      SlicedEllOperator        REPLICATED (P(None, ...) everywhere): the
                                global nnz sort breaks contiguous row
                                ownership, and the payload is the
                                COMPRESSED form — its sharded ``__call__``
                                slices local rows itself (halo path when
                                the bandwidth bound allows, all-gather
                                otherwise)
    """
    if isinstance(op, op_mod.DenseOperator):
        return op_mod.DenseOperator(P(axis, None), op.backend)
    if isinstance(op, op_mod.SparseOperator):
        return op_mod.SparseOperator(P(axis, None), P(axis, None),
                                     op.backend, op.halo)
    if isinstance(op, op_mod.BandedOperator):
        return op_mod.BandedOperator(P(None, axis), op.offsets, op.backend)
    if isinstance(op, op_mod.SlicedEllOperator):
        return op_mod.SlicedEllOperator(
            tuple(P(None, None) for _ in op.bin_values),
            tuple(P(None, None) for _ in op.bin_cols),
            P(None), op.backend, op.halo, op.slice_height, op.identity_perm)
    raise TypeError(
        f"gmres_sharded needs an explicit-storage operator (Dense/Sparse/"
        f"Banded/SlicedEll) or a dense array; got {type(op).__name__} — "
        f"matrix-free operators already compose with shard_map directly via "
        f"gmres(..., axis_name=...)")


def _run_sharded(mesh: Mesh, axis: str, op, b, x0, caller: str, body):
    """Shared wrapper skeleton of the sharded entry points.

    Validates divisibility, shards (op, b, x0) by ``shard_specs``, runs
    ``body(op_local, b_local, x0_local) -> GmresResult`` per shard inside
    the dispatch layer's ``shard_context``, and returns the global x
    row-sharded over ``axis`` — each device keeps the shard it solved, so
    no collective gathers it.  The entry points below differ ONLY in
    which shared cycle ``body`` calls.
    """
    nshards = mesh.shape[axis]
    n = b.shape[0]
    if n % nshards:
        raise ValueError(f"{caller}: n={n} not divisible by the "
                         f"{nshards}-way mesh axis")
    if op.shape[0] != n:
        raise ValueError(f"{caller}: operator {op.shape} vs b {b.shape}")
    if x0 is None:
        x0 = jnp.zeros_like(b)

    def solve_local(op_local, b_local, x0_local):
        with tuning.shard_context(axis, nshards):
            return body(op_local, b_local, x0_local)

    # Mirrors GmresResult's pytree EXACTLY (including Diagnostics): a new
    # result field needs a spec here or shard_map rejects the body's
    # output.  Everything but x is replicated scalars/rings — the
    # psum-completed betas are identical on every shard.
    out_specs = GmresResult(
        x=P(axis), residual=P(), restarts=P(), converged=P(), inner_steps=P(),
        done=P(),
        diagnostics=Diagnostics(status=P(), residual_history=P(),
                                history_len=P()),
    )
    fn = jax.shard_map(
        solve_local,
        mesh=mesh,
        in_specs=(shard_specs(op, axis), P(axis), P(axis)),
        out_specs=out_specs,
        check_vma=False,
    )
    return fn(op, b, x0)


def _local_block_jacobi(a_local: jax.Array, axis: str):
    """Shard-LOCAL block-Jacobi preconditioner: each shard factorizes its

    own diagonal block of A and applies it with ZERO communication.  This
    is the distributed-optimization lever for Krylov methods: every Arnoldi
    step costs one operand exchange, so cutting steps k-fold cuts
    collective rounds k-fold while the preconditioner itself stays
    collective-free (SSPerf hillclimb 3).
    """
    rows, n = a_local.shape
    p = lax.axis_index(axis)
    block = lax.dynamic_slice(a_local, (0, p * rows), (rows, rows))
    lu, piv = jax.scipy.linalg.lu_factor(block)

    def apply(v_local):
        return jax.scipy.linalg.lu_solve((lu, piv), v_local)

    return apply


_SHARD_PRECONDS = ("block_jacobi", "jacobi", "chebyshev",
                   "banded_block_jacobi")


def _resolve_shard_precond(precond, op, caller: str):
    """Resolve ``precond=`` for the sharded wrappers, EAGERLY.

    Returns ``build(op_local, axis) -> callable | None`` to run inside the
    shard_map body.  Strings name the built-in shard-safe members: any
    eager setup (Chebyshev interval estimation, the dense block-Jacobi
    check) happens HERE against the global operator, and the per-shard
    ``rebind`` swaps in local storage inside the trace.  A
    ``Preconditioner`` instance must be ``shard_aware`` — anything else
    raises NOW, at the call boundary, instead of failing inside shard_map
    (or worse, silently applying a global-frame M^{-1} to local shards).
    """
    if precond is None:
        return lambda op_local, axis: None
    from repro.core import preconditioners as pc_mod
    if isinstance(precond, str):
        if precond not in _SHARD_PRECONDS:
            raise ValueError(
                f"{caller}: unknown precond {precond!r}; options: "
                f"{[None, *_SHARD_PRECONDS]}")
        if precond == "block_jacobi":
            if not isinstance(op, op_mod.DenseOperator):
                raise ValueError(
                    f"{caller}: precond='block_jacobi' needs a dense "
                    f"operator (it factorizes the diagonal block of A); "
                    f"banded operators take 'banded_block_jacobi'")
            return lambda op_local, axis: _local_block_jacobi(
                op_local.a, axis)
        if precond == "banded_block_jacobi":
            if not isinstance(op, op_mod.BandedOperator):
                raise ValueError(
                    f"{caller}: precond='banded_block_jacobi' needs a "
                    f"BandedOperator (its setup walks the band pattern); "
                    f"dense operators take 'block_jacobi'")
            pc = pc_mod.banded_block_jacobi(op)
        elif precond == "jacobi":
            pc = pc_mod.jacobi(op)
        else:
            pc = pc_mod.chebyshev(op)
        return lambda op_local, axis: pc.rebind(op_local)
    if getattr(precond, "shard_aware", False):
        return lambda op_local, axis: precond.rebind(op_local)
    raise ValueError(
        f"{caller}: precond {getattr(precond, 'name', precond)!r} is not "
        f"shard-aware; pass one of {list(_SHARD_PRECONDS)} or a "
        f"Preconditioner with shard_aware=True (e.g. chebyshev, jacobi, "
        f"banded_block_jacobi) — banded_ilu0's sweeps recur across the "
        f"whole row range and cannot be sharded")


def gmres_sharded(
    mesh: Mesh,
    axis: str,
    a,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    m: int = 30,
    tol: float = 1e-5,
    max_restarts: int = 50,
    gs: str = "cgs2_fused",
    precond=None,
    compute_dtype=None,
) -> GmresResult:
    """Solve Ax=b with the operator row-sharded over ``axis`` of ``mesh``.

    ``a`` may be a GLOBAL dense (n, n) array or any explicit operator
    (``DenseOperator`` / ``SparseOperator`` / ``BandedOperator``) holding
    global storage — the wrapper derives the row-sharding specs from the
    operator type (``shard_specs``) and the per-shard communication
    pattern comes from the operator's own shard-aware mat-vec (all-gather
    for dense, ppermute halo exchange for banded/ELL).  ``b`` is global
    (n,).  Returns a ``GmresResult`` whose x is row-sharded over ``axis``
    and whose scalars are replicated.

    The default ``gs="cgs2_fused"`` runs the split-phase CGS2 kernel pair
    per shard (project kernel, h psum, update kernel); it degrades to the
    psum-correct jnp ``cgs2`` wherever Pallas is unavailable, so the
    default is safe on any backend.

    ``precond``: None | "block_jacobi" (dense; shard-local LU of the
    diagonal block) | "banded_block_jacobi" (banded; shard-local ILU(0)
    sweeps) | "jacobi" | "chebyshev" (``order`` mat-vecs through the
    halo-exchange path — ppermutes only, ZERO extra psums, so the
    pipelined one-psum-per-step count is preserved) | any ``shard_aware``
    ``Preconditioner`` instance (rebound per shard).  Everything else
    raises ``ValueError`` here, before the shard_map trace.
    """
    op = op_mod.as_operator(a)
    build_pc = _resolve_shard_precond(precond, op, "gmres_sharded")

    def body(op_local, b_local, x0_local):
        return gmres(
            op_local, b_local, x0_local, m=m, tol=tol,
            max_restarts=max_restarts, gs=gs, axis_name=axis,
            precond=build_pc(op_local, axis), compute_dtype=compute_dtype,
        )

    return _run_sharded(mesh, axis, op, b, x0, "gmres_sharded", body)


def gmres_sstep_sharded(
    mesh: Mesh,
    axis: str,
    a,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    s: int = 4,
    blocks: int = 5,
    tol: float = 1e-5,
    max_restarts: int = 30,
    gs: str = "cgs2",
    precond=None,
) -> GmresResult:
    """Row-sharded s-step GMRES — the communication-avoiding wrapper.

    Same thin shard_map-over-the-shared-cycle shape as ``gmres_sharded``,
    driving ``core.sstep.gmres_sstep``.  On banded operators the block
    step runs the halo matrix-powers kernel (ONE neighbor exchange + ONE
    psum for all s powers) and the split-phase block-GS pair — per s
    steps that is 4 collective rounds where the standard sharded cycle
    pays ~4 PER step.  ``gs="cgs2_pipelined"`` fuses each block-GS pass's
    C and Gram psums into ONE stacked payload reduction (6 -> 4 rounds
    per block; see ``core.sstep.gmres_sstep``).

    ``precond`` accepts the same options as ``gmres_sharded`` (resolved by
    the same ``_resolve_shard_precond``); a non-identity M^{-1} moves the
    power block onto the psum-per-power reference over ``A M^{-1}`` (the
    CA halo-powers kernel streams A's own bands), so preconditioning here
    trades the 2-round block for FEWER blocks — the steps-vs-cost rows in
    ``core/strategies.py`` quantify the trade.
    """
    op = op_mod.as_operator(a)
    build_pc = _resolve_shard_precond(precond, op, "gmres_sstep_sharded")

    def body(op_local, b_local, x0_local):
        return gmres_sstep(op_local, b_local, x0_local, s=s, blocks=blocks,
                           tol=tol, max_restarts=max_restarts,
                           axis_name=axis, gs=gs,
                           precond=build_pc(op_local, axis))

    return _run_sharded(mesh, axis, op, b, x0, "gmres_sstep_sharded", body)


def make_sharded_solver(mesh: Mesh, axis: str, n: int, *, m: int = 30,
                        tol: float = 1e-5, max_restarts: int = 50,
                        gs: str = "cgs2_fused", operator=None):
    """jit-compiled sharded solver with explicit in/out shardings.

    This is the entry the launcher and the dry-run lower: the operator and
    b arrive already device-sharded (NamedSharding derived from the same
    ``shard_specs`` the solver uses), nothing is re-laid-out at the
    boundary.  ``operator``: a template operator whose TYPE/static fields
    determine the shardings — pass e.g. a ``BandedOperator`` to lower the
    stencil solver; the default (None) keeps the raw dense-array calling
    convention, ``solver(a, b)`` with a global (n, n) array.
    """
    from jax.sharding import NamedSharding

    solve = functools.partial(
        gmres_sharded, mesh, axis, m=m, tol=tol, max_restarts=max_restarts,
        gs=gs,
    )
    if operator is None:
        op_sh = NamedSharding(mesh, P(axis, None))   # raw (n, n) array
    else:
        specs = shard_specs(operator, axis)
        op_sh = jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), specs)
    b_sh = NamedSharding(mesh, P(axis))
    return jax.jit(solve, in_shardings=(op_sh, b_sh))
