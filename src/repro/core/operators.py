"""Linear operator abstractions for the GMRES solver suite.

The paper solves dense ``Ax = b``; production Krylov use is matrix-free
(Newton--Krylov, preconditioned operators) and — above all — sparse:
discretized PDEs where A has O(n) nonzeros and SpMV throughput, not dense
GEMV, dominates the solve.  Five operator classes cover the spectrum:

  DenseOperator      explicit (n, n) matrix (the paper's setting)
  SparseOperator     ELL-format general sparsity (values/cols, fixed width)
  SlicedEllOperator  SELL-C-sigma-style sliced ELL: rows sorted by nonzero
                     count into fixed-height slices, each padded only to
                     its own widest row — the irregular-sparsity format
                     (power-law graphs, where plain ELL's pad-to-widest
                     is pathological)
  BandedOperator     DIA-style band stack + static diagonal offsets
                     (five/seven-point stencils, convection-diffusion)
  FunctionOperator   matrix-free ``v -> A @ v`` callable

Every explicit-storage operator takes ``backend="jnp" | "pallas"``: the
pallas backend routes mat-vecs through the tiled VMEM kernels
(kernels/matvec.py for dense, kernels/spmv.py for sparse/banded) under the
shared ``kernels.tuning.kernel_mode()`` policy — compiled on TPU,
interpret mode on CPU, and a silent degrade to the jnp reference on other
backends or when the working set exceeds VMEM.  The solvers
(``gmres``, ``gmres_batched``, ``newton_krylov``) only ever call the
operator, so sparse systems ride the same code path as dense ones.

Operators are registered as pytrees so they can be passed through
``jax.jit`` / ``vmap`` / ``shard_map`` boundaries with their array
payloads traced and their format/backend metadata static.

ROW-SHARDED execution (PR 5): inside a ``kernels.tuning.shard_context``
(the distributed solvers set it around their shard_map bodies) every
explicit operator treats its payload as the LOCAL row block and its
operand/result as local shards, and dispatches the per-shard
communication pattern itself:

  DenseOperator     all-gather the operand (dense rows touch every
                    column), then the usual tiled local GEMV/GEMM
  BandedOperator    ``halo_exchange`` of the operand's ``halo`` boundary
                    rows (2 neighbor ppermutes, O(halo) bytes), then the
                    stencil kernel over the halo-padded resident shard
  SparseOperator    same halo exchange — the static ``halo`` field bounds
                    max |col - row|, columns are remapped to halo-local
                    coordinates; operators without a halo bound (or wider
                    than a shard) fall back to all-gather + the reference

so the solver layer stays one code path: ``gmres(..., axis_name=...)``
calls the operator exactly like the single-device solve does.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseOperator:
    """Explicit dense matrix operator (the paper's setting).

    ``backend`` selects the mat-vec execution path:

      "jnp"    — ``a @ v`` (XLA-lowered reference; always available)
      "pallas" — the tiled VMEM-streaming kernels (kernels/matvec.py):
                 ``matvec`` for (n,) operands, ``block_matvec`` for (n, k)
                 multi-RHS blocks (ONE shared HBM stream of A for all k
                 columns).  Tile sizes come from the VMEM autotuner
                 (kernels/tuning.py); on CPU the kernel runs in interpret
                 mode, and on backends without Pallas support the call
                 silently degrades to the jnp path.
    """

    a: jax.Array  # (n, n) — or the LOCAL (n/P, n) row block under a
    #               ``tuning.shard_context`` (see module docstring)
    backend: str = "jnp"  # "jnp" | "pallas"

    def __call__(self, v: jax.Array) -> jax.Array:
        # v: (n,) or (n, k) — local shards under a shard_context.
        from repro.kernels import tuning

        axis = tuning.shard_axis()
        if axis is not None:
            # Dense rows touch every column: the operand gather is
            # irreducible.  After it, the local row-block product is the
            # ordinary kernel/jnp path below.
            v = lax.all_gather(v, axis, tiled=True)
        if self.backend == "pallas":
            mode = tuning.kernel_mode()
            if mode != "ref":
                from repro.kernels import matvec as matvec_k

                m, n = self.a.shape
                k = 1 if v.ndim == 1 else v.shape[1]
                bm, bn = tuning.choose_matvec_blocks(
                    m, n, jnp.dtype(self.a.dtype).name, k=k)
                kw = dict(block_m=bm, block_n=bn,
                          interpret=mode == "interpret")
                if v.ndim == 1:
                    return matvec_k.matvec(self.a, v, **kw)
                return matvec_k.block_matvec(self.a, v, **kw)
        return self.a @ v

    @property
    def shape(self):
        return self.a.shape

    @property
    def dtype(self):
        return self.a.dtype

    def tree_flatten(self):
        return (self.a,), self.backend

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux if aux is not None else "jnp")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SparseOperator:
    """ELL-format sparse operator: fixed-width per-row nonzeros.

    Row i stores its nonzero values in ``values[i, :]`` with their column
    indices in ``cols[i, :]``, zero-padded to the shared ``width`` (padding
    slots hold value 0 at column 0, keeping every gather in-bounds).  The
    rectangular layout is what the TPU row-blocked kernel wants — each
    (block_m, width) tile is dense in VMEM — at the classic ELL cost of
    padding all rows to the widest one.

    ``backend`` selects the mat-vec execution path:

      "jnp"    — gather-and-reduce reference (XLA-lowered; always available)
      "pallas" — the row-blocked gather kernel (kernels/spmv.py) with the
                 operand x held VMEM-resident; block size from
                 ``tuning.choose_spmv_block``.  The kernel runs in the
                 Pallas interpreter on CPU only: Mosaic lowers no in-kernel
                 gather, so on the chip (``tuning.gather_kernel_mode``),
                 or when x does not fit VMEM (``tuning.spmv_fits``), the
                 call takes the jnp path.

    ``__call__`` accepts (n,) vectors or (n, k) multi-RHS blocks (one
    stream of the matrix feeds all k lanes — ``gmres_batched`` rides
    this).  dtype semantics match dense ``a @ v``: the result is the
    promoted (values, v) dtype with f32 accumulation, so bf16 ``values``
    halve matrix traffic without quantizing an f32 operand.

    ``halo`` is the STATIC matrix bandwidth — an upper bound on
    max |col - row| over the NONZERO entries (padding slots excepted).
    ``from_dense`` / ``BandedOperator.to_ell`` record it automatically;
    it is what lets the row-sharded solve replace the all-gather of the
    operand with a fixed-width neighbor halo exchange.  ``halo=None``
    (unknown structure) keeps sharded solves correct via the all-gather
    fallback.
    """

    values: jax.Array   # (n, width) — LOCAL row block under shard_context
    cols: jax.Array     # (n, width) int32, GLOBAL column indices
    backend: str = "jnp"
    halo: Optional[int] = None   # static bandwidth bound (aux data)

    def __call__(self, v: jax.Array) -> jax.Array:
        from repro.kernels import spmv, tuning

        n, width = self.values.shape
        k = 1 if v.ndim == 1 else v.shape[1]
        axis = tuning.shard_axis()
        if axis is not None:
            return self._sharded_call(v, axis, n, width, k)
        if self.backend == "pallas":
            mode = tuning.gather_kernel_mode()
            if mode != "ref" and tuning.spmv_fits(n, width,
                                                  self.values.dtype, k=k):
                bm = tuning.choose_spmv_block(
                    n, width, jnp.dtype(self.values.dtype).name, k=k)
                return spmv.ell_matvec(self.values, self.cols, v,
                                       block_m=bm,
                                       interpret=mode == "interpret")
        return spmv.ell_matvec_ref(self.values, self.cols, v)

    def _sharded_call(self, v: jax.Array, axis: str, n: int, width: int,
                      k: int) -> jax.Array:
        """Row-sharded SpMV: halo exchange + per-shard kernel.

        ``self`` holds the local (n_local, width) row block with GLOBAL
        column indices; ``v`` the matching (n_local, ...) operand shard.
        Without a usable ``halo`` bound (None, or wider than a shard) the
        operand is all-gathered instead — correct for any structure.
        """
        from repro.kernels import spmv, tuning

        halo = self.halo
        if halo is None or halo > n:
            x_full = lax.all_gather(v, axis, tiled=True)
            return spmv.ell_matvec_ref(self.values, self.cols, x_full)
        # Remap global columns into the halo-padded local frame.  Real
        # nonzeros land in [0, n + 2*halo) by the bandwidth bound; padding
        # slots (value 0 at global column 0) clip to 0 and contribute 0.
        # The remap is a pure function of solve constants, so XLA's
        # while-loop LICM hoists it out of the Arnoldi loop (verified in
        # the lowered HLO); do NOT cache the result on the instance —
        # axis_index is a tracer, and a cached tracer leaks across traces.
        p = lax.axis_index(axis)
        cols_local = jnp.clip(self.cols - p * n + halo, 0,
                              n + 2 * halo - 1).astype(jnp.int32)
        x_halo = spmv.halo_exchange(v, halo, axis, tuning.shard_size())
        mode = tuning.gather_kernel_mode()
        if (self.backend == "pallas" and mode != "ref"
                and tuning.spmv_fits(n, width, self.values.dtype, k=k,
                                     halo=halo)):
            bm = tuning.choose_spmv_block(
                n, width, jnp.dtype(self.values.dtype).name, k=k, halo=halo)
            return spmv.ell_matvec_halo(self.values, cols_local, x_halo,
                                        block_m=bm,
                                        interpret=mode == "interpret")
        return spmv.ell_matvec_ref(self.values, cols_local, x_halo)

    @classmethod
    def from_dense(cls, a, *, width: int | None = None,
                   backend: str = "jnp") -> "SparseOperator":
        """Compress a dense (n, n) matrix to ELL form.

        ``width`` defaults to the widest row's nonzero count; passing a
        smaller width raises rather than silently dropping entries.  The
        static ``halo`` (bandwidth) bound for the row-sharded path is
        recorded from the nonzero pattern.
        """
        a_np = np.asarray(a)
        n = a_np.shape[0]
        mask = a_np != 0
        max_nnz = int(mask.sum(axis=1).max()) if n else 0
        if width is None:
            width = max(max_nnz, 1)
        elif width < max_nnz:
            raise ValueError(f"from_dense: width={width} < widest row "
                             f"({max_nnz} nonzeros) — entries would be "
                             f"dropped")
        # Stable argsort puts each row's nonzero columns first, in order.
        order = np.argsort(~mask, axis=1, kind="stable")[:, :width]
        vals = np.take_along_axis(a_np, order, axis=1)
        keep = np.take_along_axis(mask, order, axis=1)
        rows, nz_cols = np.nonzero(mask)
        halo = int(np.abs(nz_cols - rows).max()) if rows.size else 0
        return cls(jnp.asarray(np.where(keep, vals, 0).astype(a_np.dtype)),
                   jnp.asarray(np.where(keep, order, 0).astype(np.int32)),
                   backend, halo)

    def todense(self) -> jax.Array:
        """Materialize the dense (n, n) matrix (tests / small systems)."""
        n, width = self.values.shape
        rows = jnp.repeat(jnp.arange(n), width)
        return (jnp.zeros((n, n), self.values.dtype)
                .at[rows, self.cols.reshape(-1)]
                .add(self.values.reshape(-1)))

    @property
    def shape(self):
        n = self.values.shape[0]
        return (n, n)

    @property
    def dtype(self):
        return self.values.dtype

    def tree_flatten(self):
        return (self.values, self.cols), (self.backend, self.halo)

    @classmethod
    def tree_unflatten(cls, aux, children):
        backend, halo = aux if aux is not None else ("jnp", None)
        return cls(children[0], children[1], backend, halo)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BandedOperator:
    """DIA-style banded operator: ``y[i] = sum_d bands[d, i] * x[i + off_d]``.

    ``bands`` is (nbands, n) — band d holds the matrix entries
    ``A[i, i + offsets[d]]`` at index i — and ``offsets`` is a STATIC tuple
    of diagonal shifts (pytree aux data, so jit retraces on a new stencil
    shape but not on new band values).  Out-of-range reads contribute zero,
    which makes Dirichlet boundaries free: band entries at the grid edge
    simply face a zero halo.

    ``backend`` selects the mat-vec execution path:

      "jnp"    — shifted-window reference (XLA-lowered; always available)
      "pallas" — the stencil kernel (kernels/spmv.py): pure VPU work over
                 shifted windows of a lane-dense VMEM-resident x, no
                 gather.  Interpret mode on CPU; the jnp path where Pallas
                 is unavailable or the padded operand exceeds VMEM
                 (``tuning.banded_fits``).

    Accepts (n,) or (n, k) operands; dtype semantics match dense ``a @ v``
    (promoted dtype out, f32 accumulation inside).
    """

    bands: jax.Array    # (nbands, n)
    offsets: tuple      # static, len == nbands
    backend: str = "jnp"

    def __call__(self, v: jax.Array) -> jax.Array:
        from repro.kernels import spmv, tuning

        nbands, n = self.bands.shape
        halo = max(abs(int(o)) for o in self.offsets)
        k = 1 if v.ndim == 1 else v.shape[1]
        axis = tuning.shard_axis()
        if axis is not None:
            return self._sharded_call(v, axis, n, nbands, halo, k)
        if self.backend == "pallas":
            mode = tuning.kernel_mode()
            if mode != "ref" and tuning.banded_fits(n, nbands,
                                                    self.bands.dtype,
                                                    halo=halo, k=k):
                bm = tuning.choose_banded_block(
                    n, nbands, jnp.dtype(self.bands.dtype).name,
                    halo=halo, k=k)
                return spmv.banded_matvec(self.bands, v, self.offsets,
                                          block_m=bm,
                                          interpret=mode == "interpret")
        return spmv.banded_matvec_ref(self.bands, v, self.offsets)

    def _sharded_call(self, v: jax.Array, axis: str, n: int, nbands: int,
                      halo: int, k: int) -> jax.Array:
        """Row-sharded stencil SpMV: ppermute halo exchange + local kernel.

        ``self.bands`` holds the local (nbands, n_local) column block of
        the band stack; out-of-range reads at the GLOBAL edges see the
        zeros ``halo_exchange`` leaves on edge shards, so the semantics
        match the single-device kernel exactly.  A stencil wider than a
        shard (halo > n_local — pathological) falls back to an all-gather
        window.
        """
        from repro.kernels import spmv, tuning

        if halo > n:
            x_full = lax.all_gather(v, axis, tiled=True)
            pad = ((halo, halo), (0, 0)) if x_full.ndim == 2 else (halo, halo)
            x_pad = jnp.pad(x_full, pad)
            start = lax.axis_index(axis) * n
            sizes = ((n + 2 * halo,) if x_full.ndim == 1
                     else (n + 2 * halo, x_full.shape[1]))
            starts = (start,) if x_full.ndim == 1 else (start, 0)
            x_halo = lax.dynamic_slice(x_pad, starts, sizes)
        else:
            x_halo = spmv.halo_exchange(v, halo, axis, tuning.shard_size())
        mode = tuning.kernel_mode()
        if (self.backend == "pallas" and mode != "ref"
                and tuning.banded_fits(n, nbands, self.bands.dtype,
                                       halo=halo, k=k)):
            bm = tuning.choose_banded_block(
                n, nbands, jnp.dtype(self.bands.dtype).name, halo=halo, k=k)
            return spmv.banded_matvec_halo(self.bands, x_halo, self.offsets,
                                           block_m=bm,
                                           interpret=mode == "interpret")
        return spmv.banded_matvec_halo_ref(self.bands, x_halo, self.offsets)

    def to_ell(self, backend: str | None = None) -> SparseOperator:
        """Convert to ELL form (width = nbands; OOB slots become padding)."""
        nbands, n = self.bands.shape
        i = jnp.arange(n)
        cols = jnp.stack([i + off for off in self.offsets], axis=1)
        valid = (cols >= 0) & (cols < n)
        vals = jnp.where(valid, self.bands.T, 0)
        halo = max((abs(int(o)) for o in self.offsets), default=0)
        return SparseOperator(vals, jnp.where(valid, cols, 0).astype(jnp.int32),
                              self.backend if backend is None else backend,
                              halo)

    def todense(self) -> jax.Array:
        """Materialize the dense (n, n) matrix (tests / small systems)."""
        nbands, n = self.bands.shape
        a = jnp.zeros((n, n), self.bands.dtype)
        for d, off in enumerate(self.offsets):
            band = self.bands[d]
            if off >= 0:
                a = a + jnp.diag(band[:n - off], k=off)
            else:
                a = a + jnp.diag(band[-off:], k=off)
        return a

    @property
    def shape(self):
        n = self.bands.shape[1]
        return (n, n)

    @property
    def dtype(self):
        return self.bands.dtype

    def tree_flatten(self):
        return (self.bands,), (self.offsets, self.backend)

    @classmethod
    def tree_unflatten(cls, aux, children):
        offsets, backend = aux
        return cls(children[0], offsets, backend)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SlicedEllOperator:
    """Sliced-ELL (SELL-C-sigma-style) operator for irregular row patterns.

    Plain ELL pads EVERY row to the widest row's nonzero count — fine for
    stencils, pathological for power-law graphs where one hub row inflates
    storage and HBM traffic for all n rows.  Sliced ELL sorts rows by
    nonzero count (descending, stable), cuts the sorted order into
    fixed-height slices of ``slice_height`` rows, pads each slice only to
    its own widest row, and keeps a permutation to recover the original
    row order.  Consecutive same-width slices (the common case after the
    sort) are stored as ONE rectangle, so the payload is a short tuple of
    width BINS:

      bin_values[b]  (rows_b, width_b)  values, sorted-row frame
      bin_cols[b]    (rows_b, width_b)  int32 GLOBAL column indices
      perm           (n,) int32 — perm[i] = original row at sorted slot i

    The mat-vec is one row-binned gather kernel launch per bin
    (``kernels/spmv.sell_matvec``; a handful of launches — the builder
    agglomerates bins to ``max_bins``) over the shared VMEM-resident
    operand, then a scatter through ``perm`` back to original order.
    Traffic is proportional to sum_b rows_b*width_b instead of
    n*max_width — the whole point of the format.

    When sorting would NOT shrink storage (near-uniform row lengths: the
    stencils), ``from_dense``/``from_ell`` keep the ORIGINAL row order
    (``sort="auto"`` — sigma = 1 in SELL-C-sigma terms) so ``perm`` is the
    identity, the scatter disappears, and the layout degenerates to plain
    ELL with per-slice widths: sliced ELL is never worse where ELL was
    already tight.

    Row-sharded solves (``shard_specs`` replicates the payload — the
    global sort breaks contiguous row ownership, and the payload is the
    COMPRESSED form): with a usable ``halo`` bound the operator
    re-materializes its plain-ELL row table once per trace (hoisted out
    of the Arnoldi loop by XLA LICM, same argument as SparseOperator's
    column remap), slices the local row block, and rides the standard
    neighbor halo exchange; otherwise it all-gathers the operand and
    slices the local output rows.  Power-law graphs have halo ~ n (the
    hub touches everything), so they take the all-gather path — which is
    what their structure demands.
    """

    bin_values: tuple   # of (rows_b, width_b) arrays, nnz-sorted row frame
    bin_cols: tuple     # of (rows_b, width_b) int32, GLOBAL columns
    perm: jax.Array     # (n,) int32
    backend: str = "jnp"
    halo: Optional[int] = None      # static bandwidth bound (aux data)
    slice_height: int = 64          # C in SELL-C-sigma (aux data)
    identity_perm: bool = False     # static: builder kept original order

    def __call__(self, v: jax.Array) -> jax.Array:
        from repro.kernels import tuning

        k = 1 if v.ndim == 1 else v.shape[1]
        axis = tuning.shard_axis()
        if axis is not None:
            return self._sharded_call(v, axis, k)
        return self._unsort(self._sorted_matvec(v, k))

    def _sorted_matvec(self, v: jax.Array, k: int) -> jax.Array:
        """Per-bin SpMV producing the output in the SORTED row frame."""
        from repro.kernels import spmv, tuning

        n = self.perm.shape[0]
        if self.backend == "pallas":
            mode = tuning.gather_kernel_mode()
            if mode != "ref" and tuning.sell_fits(n, self.max_width,
                                                  self.dtype, k=k):
                bms = tuple(
                    tuning.choose_sell_block(
                        n, vals.shape[0], vals.shape[1],
                        jnp.dtype(vals.dtype).name, k=k,
                        slice_height=self.slice_height)
                    for vals in self.bin_values)
                return spmv.sell_matvec(self.bin_values, self.bin_cols, v,
                                        block_ms=bms,
                                        interpret=mode == "interpret")
        return spmv.sell_matvec_ref(self.bin_values, self.bin_cols, v)

    def _unsort(self, y_sorted: jax.Array) -> jax.Array:
        if self.identity_perm:
            return y_sorted
        return jnp.zeros_like(y_sorted).at[self.perm].set(y_sorted)

    def _sharded_call(self, v: jax.Array, axis: str, k: int) -> jax.Array:
        """Row-sharded matvec over the REPLICATED sliced payload.

        ``v`` is the local (n/P, ...) operand shard; the result is the
        matching local output shard.  See the class docstring for the two
        communication patterns (halo vs all-gather).
        """
        from repro.kernels import spmv, tuning

        nl = v.shape[0]
        halo = self.halo
        p = lax.axis_index(axis)
        if halo is not None and halo <= nl:
            # Same per-shard pattern as SparseOperator, over the plain-ELL
            # row table re-materialized from the bins: a pure function of
            # solve constants, so XLA LICM hoists it out of the solver's
            # while_loop — the trade is plain-ELL-padded LOCAL traffic for
            # O(halo) exchanged bytes.
            vals, cols = self.to_ell_arrays()
            width = vals.shape[1]
            vals_l = lax.dynamic_slice_in_dim(vals, p * nl, nl, 0)
            cols_l = lax.dynamic_slice_in_dim(cols, p * nl, nl, 0)
            cols_local = jnp.clip(cols_l - p * nl + halo, 0,
                                  nl + 2 * halo - 1).astype(jnp.int32)
            x_halo = spmv.halo_exchange(v, halo, axis, tuning.shard_size())
            mode = tuning.gather_kernel_mode()
            if (self.backend == "pallas" and mode != "ref"
                    and tuning.spmv_fits(nl, width, self.dtype, k=k,
                                         halo=halo)):
                bm = tuning.choose_spmv_block(
                    nl, width, jnp.dtype(self.dtype).name, k=k, halo=halo)
                return spmv.ell_matvec_halo(vals_l, cols_local, x_halo,
                                            block_m=bm,
                                            interpret=mode == "interpret")
            return spmv.ell_matvec_ref(vals_l, cols_local, x_halo)
        x_full = lax.all_gather(v, axis, tiled=True)
        y = self._unsort(self._sorted_matvec(x_full, k))
        return lax.dynamic_slice_in_dim(y, p * nl, nl, 0)

    # -- format conversions -------------------------------------------------
    @classmethod
    def from_dense(cls, a, *, slice_height: int = 64, backend: str = "jnp",
                   sort: bool | str = "auto",
                   max_bins: int = 8) -> "SlicedEllOperator":
        """Compress a dense (n, n) matrix to sliced-ELL form.

        Handles UNSTRUCTURED nonzero patterns: each row's nonzeros are
        packed independently and the static ``halo`` (bandwidth) bound is
        recorded from the pattern, exactly like ``SparseOperator.
        from_dense``.  ``sort="auto"`` sorts rows by nonzero count only
        when that shrinks slice storage by >= 10% (see class docstring);
        pass True/False to force.  Host-side numpy, like every
        ``from_dense`` here.
        """
        a_np = np.asarray(a)
        n = a_np.shape[0]
        mask = a_np != 0
        nnz = mask.sum(axis=1)
        wtab = max(int(nnz.max()) if n else 0, 1)
        order = np.argsort(~mask, axis=1, kind="stable")[:, :wtab]
        vals = np.take_along_axis(a_np, order, axis=1)
        keep = np.take_along_axis(mask, order, axis=1)
        row_vals = np.where(keep, vals, 0).astype(a_np.dtype)
        row_cols = np.where(keep, order, 0)
        rows, nz_cols = np.nonzero(mask)
        halo = int(np.abs(nz_cols - rows).max()) if rows.size else 0
        return cls._build(row_vals, row_cols, nnz, slice_height, backend,
                          halo, sort=sort, max_bins=max_bins)

    @classmethod
    def from_ell(cls, sp: SparseOperator, *, slice_height: int = 64,
                 backend: str | None = None, sort: bool | str = "auto",
                 max_bins: int = 8) -> "SlicedEllOperator":
        """Re-slice a plain-ELL operator (value-0 slots become padding).

        Genuine stored zeros are dropped — same semantics as
        ``from_dense`` on the materialized matrix.
        """
        vals_np = np.asarray(sp.values)
        cols_np = np.asarray(sp.cols)
        mask = vals_np != 0
        nnz = mask.sum(axis=1)
        # Pack each row's nonzero slots first (stable, order-preserving).
        order = np.argsort(~mask, axis=1, kind="stable")
        keep = np.take_along_axis(mask, order, axis=1)
        row_vals = np.where(keep, np.take_along_axis(vals_np, order, 1), 0)
        row_cols = np.where(keep, np.take_along_axis(cols_np, order, 1), 0)
        halo = sp.halo
        if halo is None:
            r, c = np.nonzero(mask)
            halo = int(np.abs(cols_np[r, c] - r).max()) if r.size else 0
        return cls._build(row_vals.astype(vals_np.dtype), row_cols, nnz,
                          slice_height,
                          sp.backend if backend is None else backend,
                          halo, sort=sort, max_bins=max_bins)

    @classmethod
    def _build(cls, row_vals, row_cols, nnz, slice_height, backend, halo,
               *, sort="auto", max_bins=8) -> "SlicedEllOperator":
        """Shared host-side builder over a packed per-row nonzero table.

        ``row_vals``/``row_cols`` are (n, w) numpy arrays with each row's
        nonzeros packed FIRST (slots >= nnz[i] hold value 0 at column 0).
        Slices the (possibly sorted) row order into ``slice_height``
        chunks, then greedily merges adjacent slices until at most
        ``max_bins`` rectangles remain — each merge pads the smaller
        slice up to the wider one, and the merge order minimizes the
        padding added, so the bin count (= kernel launch count) is bounded
        while the storage stays near the per-slice optimum.
        """
        n = row_vals.shape[0]
        c = max(int(slice_height), 1)

        def slice_storage(order):
            return sum(
                len(order[s0:s0 + c]) * int(nnz[order[s0:s0 + c]].max())
                for s0 in range(0, n, c)) if n else 0

        ident = np.arange(n)
        by_nnz = np.argsort(-nnz, kind="stable")
        if sort == "auto":
            use_sort = slice_storage(by_nnz) < 0.9 * slice_storage(ident)
        else:
            use_sort = bool(sort)
        order = by_nnz if use_sort else ident
        # Per-slice exact widths (>= 1 so padding slots exist), merged
        # into [row_start, row_end, width) bins.
        bins = []
        for s0 in range(0, n, c):
            h = min(c, n - s0)
            w = max(int(nnz[order[s0:s0 + h]].max()), 1)
            if bins and bins[-1][2] == w:
                bins[-1][1] += h
            else:
                bins.append([s0, s0 + h, w])
        if not bins:
            bins = [[0, 0, 1]]

        def merge_cost(i):
            (a0, a1, aw), (b0, b1, bw) = bins[i], bins[i + 1]
            w = max(aw, bw)
            return (a1 - a0) * (w - aw) + (b1 - b0) * (w - bw)

        while len(bins) > max(int(max_bins), 1):
            i = min(range(len(bins) - 1), key=merge_cost)
            (a0, a1, aw), (b0, b1, bw) = bins[i], bins[i + 1]
            bins[i:i + 2] = [[a0, b1, max(aw, bw)]]

        bin_values, bin_cols = [], []
        for r0, r1, w in bins:
            rows = order[r0:r1]
            bin_values.append(jnp.asarray(row_vals[rows][:, :w]))
            bin_cols.append(
                jnp.asarray(row_cols[rows][:, :w].astype(np.int32)))
        return cls(tuple(bin_values), tuple(bin_cols),
                   jnp.asarray(order.astype(np.int32)), backend, halo,
                   c, bool(np.array_equal(order, ident)))

    def to_ell_arrays(self):
        """Plain-ELL (values, cols) row table in ORIGINAL row order.

        Width = the widest bin.  Pure jnp — usable under jit/shard_map,
        where it is a function of solve constants and gets hoisted out of
        solver loops (the sharded halo path relies on this).
        """
        n = self.perm.shape[0]
        w = self.max_width
        vs = [jnp.pad(v, ((0, 0), (0, w - v.shape[1])))
              for v in self.bin_values]
        cs = [jnp.pad(col, ((0, 0), (0, w - col.shape[1])))
              for col in self.bin_cols]
        values = (jnp.zeros((n, w), self.dtype)
                  .at[self.perm].set(jnp.concatenate(vs, axis=0)))
        cols = (jnp.zeros((n, w), jnp.int32)
                .at[self.perm].set(jnp.concatenate(cs, axis=0)))
        return values, cols

    def to_ell(self, backend: str | None = None) -> SparseOperator:
        """Expand back to a plain-ELL operator (pad-to-widest)."""
        values, cols = self.to_ell_arrays()
        return SparseOperator(values, cols,
                              self.backend if backend is None else backend,
                              self.halo)

    def todense(self) -> jax.Array:
        """Materialize the dense (n, n) matrix (tests / small systems)."""
        n = self.perm.shape[0]
        a = jnp.zeros((n, n), self.dtype)
        start = 0
        for vals, cols in zip(self.bin_values, self.bin_cols):
            rb, wb = vals.shape
            orig = self.perm[start:start + rb]
            rows = jnp.repeat(orig, wb)
            a = a.at[rows, cols.reshape(-1)].add(vals.reshape(-1))
            start += rb
        return a

    # -- format statistics (static python ints; bench/docs read these) ------
    @property
    def max_width(self) -> int:
        return max(int(v.shape[1]) for v in self.bin_values)

    @property
    def storage_entries(self) -> int:
        """Stored slots incl. slice padding: sum_b rows_b * width_b."""
        return sum(int(v.shape[0]) * int(v.shape[1])
                   for v in self.bin_values)

    @property
    def shape(self):
        n = self.perm.shape[0]
        return (n, n)

    @property
    def dtype(self):
        return self.bin_values[0].dtype

    def tree_flatten(self):
        return ((self.bin_values, self.bin_cols, self.perm),
                (self.backend, self.halo, self.slice_height,
                 self.identity_perm))

    @classmethod
    def tree_unflatten(cls, aux, children):
        bin_values, bin_cols, perm = children
        backend, halo, slice_height, identity_perm = aux
        return cls(tuple(bin_values), tuple(bin_cols), perm, backend, halo,
                   slice_height, identity_perm)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FunctionOperator:
    """Matrix-free operator ``v -> A @ v``.

    ``captures`` holds any array payload the function closes over so that the
    operator remains a faithful pytree (jit re-tracing sees value changes).
    """

    fn: Callable[..., jax.Array]
    n: int
    captures: Any = ()

    def __call__(self, v: jax.Array) -> jax.Array:
        return self.fn(v, *self.captures) if self.captures else self.fn(v)

    @property
    def shape(self):
        return (self.n, self.n)

    def tree_flatten(self):
        return (self.captures,), (self.fn, self.n)

    @classmethod
    def tree_unflatten(cls, aux, children):
        fn, n = aux
        (captures,) = children
        return cls(fn, n, captures)


# Operators with explicit matrix storage: their (n, k) multi-RHS __call__
# lets the block solver stream the matrix ONCE for all k lanes.
EXPLICIT_OPERATORS = (DenseOperator, SparseOperator, BandedOperator,
                      SlicedEllOperator)


def with_dtype(op, dtype):
    """The same explicit operator with its matrix storage cast to ``dtype``.

    Structure (cols/offsets/perm/halo) is untouched — only the value
    stream changes.  This is how the solvers build a reduced-precision
    operand stream (``compute_dtype=bf16``) while keeping the original
    operator for full-precision residual recomputation.
    """
    if isinstance(op, DenseOperator):
        return DenseOperator(op.a.astype(dtype), op.backend)
    if isinstance(op, SparseOperator):
        return SparseOperator(op.values.astype(dtype), op.cols, op.backend,
                              op.halo)
    if isinstance(op, BandedOperator):
        return BandedOperator(op.bands.astype(dtype), op.offsets, op.backend)
    if isinstance(op, SlicedEllOperator):
        return SlicedEllOperator(
            tuple(v.astype(dtype) for v in op.bin_values), op.bin_cols,
            op.perm, op.backend, op.halo, op.slice_height, op.identity_perm)
    raise TypeError(f"with_dtype: no explicit storage on {type(op).__name__}")


def as_operator(a) -> Callable[[jax.Array], jax.Array]:
    """Normalize ``a`` to a matvec callable.

    Operator instances and callables pass through unchanged; raw arrays
    wrap into a ``DenseOperator`` on the jnp backend.
    """
    if isinstance(a, EXPLICIT_OPERATORS + (FunctionOperator,)):
        return a
    if callable(a):
        return a
    return DenseOperator(jnp.asarray(a))


def jvp_operator(f: Callable, primal, *, damping: float = 0.0) -> FunctionOperator:
    """Gauss-Newton / Hessian-free operator: ``v -> J^T J v + damping * v``.

    ``f`` maps a flat parameter vector to a flat residual vector.  The
    operator is the classic jvp/vjp sandwich used by Newton--Krylov
    optimizers; it is symmetric PSD so GMRES converges like MINRES on it.
    """
    n = primal.shape[0]

    def matvec(v, p):
        _, jv = jax.jvp(f, (p,), (v,))
        (jtjv,) = jax.vjp(f, p)[1](jv)
        return jtjv + damping * v

    return FunctionOperator(matvec, n, captures=(primal,))


def hvp_operator(loss: Callable, primal, *, damping: float = 0.0) -> FunctionOperator:
    """Hessian-vector-product operator ``v -> H v + damping v`` (matrix-free)."""
    n = primal.shape[0]

    def matvec(v, p):
        return jax.jvp(jax.grad(loss), (p,), (v,))[1] + damping * v

    return FunctionOperator(matvec, n, captures=(primal,))


def poisson_1d(n: int, dtype=jnp.float32) -> jax.Array:
    """Dense 1-D Poisson (tridiagonal) test matrix — SPD, well-conditioned rows."""
    a = (
        2.0 * jnp.eye(n, dtype=dtype)
        - jnp.eye(n, k=1, dtype=dtype)
        - jnp.eye(n, k=-1, dtype=dtype)
    )
    return a


def convection_diffusion(n: int, beta: float = 0.5, dtype=jnp.float32) -> jax.Array:
    """Nonsymmetric convection-diffusion matrix — the canonical GMRES target."""
    a = (
        2.0 * jnp.eye(n, dtype=dtype)
        + (-1.0 + beta) * jnp.eye(n, k=1, dtype=dtype)
        + (-1.0 - beta) * jnp.eye(n, k=-1, dtype=dtype)
    )
    return a


def random_diagdom(key, n: int, dtype=jnp.float32, *, dominance: float = 2.0) -> jax.Array:
    """Random nonsymmetric diagonally-dominant matrix (paper's rnorm-style setup,

    made well-conditioned so fp32 Krylov converges; the paper used random dense
    matrices from ``rnorm`` which are near-singular without dominance).
    """
    a = jax.random.normal(key, (n, n), dtype=dtype) / jnp.sqrt(n).astype(dtype)
    rowsum = jnp.abs(a).sum(axis=1)
    return a + jnp.diag(dominance * rowsum.astype(dtype))
