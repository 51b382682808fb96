"""Pallas TPU kernels: sparse / structured mat-vec (the SpMV layer).

The paper benchmarks dense ``A %*% v`` only, but the production home of
GMRES is sparse systems (Ioannidis et al. 1906.04051): discretized PDEs
where A has O(n) nonzeros and dense GEMV would waste n/nnz of the HBM
stream on zeros.  Two storage formats, chosen for TPU-style tiling:

ELL (``ell_matvec``) — general sparsity.  A is (values, cols), both
  (n, width): row i holds its nonzeros in ``values[i, :]`` with their
  column indices in ``cols[i, :]``, zero-padded to the fixed per-row
  ``width`` (padding slots point at column 0 with value 0 so the gather
  stays in-bounds).  The rectangular layout is exactly what a row-blocked
  grid wants — every (bm, width) tile is dense in VMEM — at the price of
  padding rows to the widest row (the classic ELL trade; keep ``width``
  tight or slice the matrix).  The operand x stays WHOLE in VMEM: sparse
  column patterns touch arbitrary rows of x, so tiling x would re-stream
  it once per row block, and for the O(n)-nonzero regime x is the small
  array anyway (``tuning.spmv_fits`` gates the residency).

Sliced ELL (``sell_matvec``) — irregular sparsity (SELL-C-sigma style).
  Plain ELL's pad-to-widest is pathological when row lengths span orders
  of magnitude (power-law graphs: one hub row inflates every row's
  storage).  Here rows are sorted by nonzero count, cut into fixed-height
  slices each padded only to its own widest row, and same-width slices
  are merged into a handful of rectangular width BINS — the matvec is one
  ``_ell_kernel`` launch per bin over the shared VMEM-resident operand
  (column indices stay GLOBAL, so x needs no permutation), producing the
  output in the sorted-row frame.  The caller (``SlicedEllOperator``)
  owns the row permutation and scatters the result back; traffic is
  proportional to sum_b rows_b*width_b instead of n*max_width.

Banded / stencil (``banded_matvec``) — structured grids.  A is a DIA-style
  band stack (nbands, n) plus a static tuple of diagonal ``offsets``:
  ``y[i] = sum_d bands[d, i] * x[i + offsets[d]]`` with out-of-range reads
  contributing zero.  No gather at all: each band is an elementwise product
  with a SHIFTED window of x, so the kernel is pure VPU work over dynamic
  slices of a halo-padded VMEM-resident x — the five/seven-point Poisson
  and convection-diffusion stencils hit this path.

Both kernels accept (n,) vectors or (n, k) multi-RHS blocks — one stream
of the matrix feeds all k lanes, same as ``matvec.block_matvec`` — and
both accumulate in f32 (f64 under x64) regardless of storage dtype, so a
bf16 band/values stream halves matrix traffic without quantizing x.

HBM traffic per matvec (f32, vs dense GEMV's 4*(n*n + 2n) bytes):

    ELL:    n*width*(s + 4) + 8n      (values + int32 cols + x + y)
    banded: nbands*n*s + 8n           (bands + x + y; offsets are static)

For a five-point stencil on a 256x256 grid that is ~650x less traffic than
the dense stream — the reason sparse GMRES iterations are matvec-cheap and
orthogonalization-dominated (see benchmarks/kernel_bench.py spmv rows).

ROW-SHARDED variants (PR 5).  When the matrix rows are sharded over a mesh
axis, a shard's matvec needs operand values at most ``halo`` rows beyond
its own block (halo = the matrix bandwidth, max |col - row|) — NOT the
whole vector.  ``halo_exchange`` moves exactly those boundary rows with
two ``ppermute`` rounds (neighbors only; edge shards read zeros, matching
the out-of-range-is-zero convention of the kernels), and the
``*_matvec_halo`` entry points run the SAME kernels as above over the
halo-padded LOCAL operand — VMEM-resident per shard, so the residency
fits-checks divide by the shard count while the exchanged bytes stay
O(halo), independent of n.  That is the communication picture Ioannidis
et al. (1906.04051) identify as the multi-GPU GMRES bottleneck: an
all-gather per matvec becomes a fixed-width neighbor exchange.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning
from repro.kernels.tuning import LANE, _round_up


def _acc_dtypes(mat_dtype, x_dtype):
    """(compute, accumulate) dtypes matching dense ``a @ x`` promotion."""
    compute = jnp.promote_types(mat_dtype, x_dtype)
    return compute, jnp.promote_types(compute, jnp.float32)


# --------------------------------------------------------------------------
# ELL gather kernel
# --------------------------------------------------------------------------
def _ell_kernel(vals_ref, cols_ref, x_ref, o_ref):
    vals = vals_ref[...]                     # (bm, width), storage dtype
    cols = cols_ref[...]                     # (bm, width) int32
    x = x_ref[...]                           # (n, k) — whole, VMEM-resident
    # Gather the operand rows each slot references: (bm, width, k).  The
    # matrix tile upcasts in-register so bf16 values keep their halved HBM
    # stream without quantizing x; products accumulate in o_ref's dtype.
    g = jnp.take(x, cols, axis=0).astype(o_ref.dtype)
    o_ref[...] = jnp.sum(vals[:, :, None].astype(o_ref.dtype) * g, axis=1)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def ell_matvec(values: jax.Array, cols: jax.Array, x: jax.Array, *,
               block_m: int = 512, interpret: bool = False) -> jax.Array:
    """y = A @ x for ELL-format A.  values/cols: (n, width); x: (n,) or (n, k)."""
    n, width = values.shape
    if cols.shape != (n, width):
        raise TypeError(f"ell_matvec: cols {cols.shape} must match values "
                        f"{values.shape}")
    if x.shape[0] != n:
        # Pallas pads blocks, so a length mismatch would otherwise read
        # garbage instead of raising the way ``a @ x`` does.
        raise TypeError(f"ell_matvec: values {values.shape} @ x {x.shape} — "
                        f"x must have {n} rows")
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    k = x.shape[1]
    bm = min(block_m, n)
    if n % bm:
        # Pad rows to the tile grid; padding slots carry value 0 at column 0
        # (same convention as real padding slots), so they contribute nothing.
        np_ = (n + bm - 1) // bm * bm
        out = ell_matvec(
            jnp.pad(values, ((0, np_ - n), (0, 0))),
            jnp.pad(cols, ((0, np_ - n), (0, 0))),
            jnp.pad(x, ((0, np_ - n), (0, 0))),
            block_m=bm, interpret=interpret)[:n]
        return out[:, 0] if squeeze else out

    compute_dtype, acc_dtype = _acc_dtypes(values.dtype, x.dtype)
    out = _ell_pallas(values, cols, x.astype(compute_dtype), bm, interpret,
                      acc_dtype, "gmres_spmv_ell").astype(compute_dtype)
    return out[:, 0] if squeeze else out


def _ell_pallas(values, cols, x, bm, interpret, acc_dtype, name):
    """Shared pallas_call: (n, width) values/cols row tiles, operand x
    WHOLE in VMEM — x has n rows single-device, n + 2*halo rows for the
    row-sharded variant (``cols`` then index the halo-local frame)."""
    n, width = values.shape
    k = x.shape[1]
    return pl.pallas_call(
        _ell_kernel,
        grid=(n // bm,),
        in_specs=[
            pl.BlockSpec((bm, width), lambda i: (i, 0)),
            pl.BlockSpec((bm, width), lambda i: (i, 0)),
            pl.BlockSpec((x.shape[0], k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), acc_dtype),
        interpret=interpret,
        name=name,
    )(values, cols, x)


def ell_matvec_ref(values: jax.Array, cols: jax.Array,
                   x: jax.Array) -> jax.Array:
    """Pure-jnp ELL SpMV oracle (and the ``kernel_mode() == "ref"`` path)."""
    compute_dtype, acc_dtype = _acc_dtypes(values.dtype, x.dtype)
    g = x[cols].astype(acc_dtype)            # (n, width) or (n, width, k)
    vals = values.astype(acc_dtype)
    if x.ndim == 2:
        vals = vals[:, :, None]
    return jnp.sum(vals * g, axis=1).astype(compute_dtype)


# --------------------------------------------------------------------------
# Sliced-ELL (SELL-C-sigma) row-binned kernel entry points
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("block_ms", "interpret"))
def sell_matvec(bin_values: tuple, bin_cols: tuple, x: jax.Array, *,
                block_ms: tuple | None = None,
                interpret: bool = False) -> jax.Array:
    """Sliced-ELL SpMV in the SORTED-row frame: one launch per width bin.

    ``bin_values[b]`` / ``bin_cols[b]`` are (rows_b, width_b) rectangles —
    contiguous runs of nnz-sorted rows padded to the bin's width, with
    int32 GLOBAL column indices (padding slots: value 0 at column 0).
    ``x`` is the full (n,) or (n, k) operand, resident in VMEM for every
    launch.  Returns the (sum_b rows_b,) or (sum_b rows_b, k) output in
    bin order — the caller scatters it back through its row permutation.

    ``block_ms``: optional per-bin row-block tuple (``choose_sell_block``
    per bin); each bin's row count is padded up to its block like
    ``ell_matvec`` pads the grid — but only the bin's rows, never x.
    """
    bin_values = tuple(bin_values)
    bin_cols = tuple(bin_cols)
    if not bin_values or len(bin_values) != len(bin_cols):
        raise TypeError(f"sell_matvec: {len(bin_values)} value bins vs "
                        f"{len(bin_cols)} cols bins (need >= 1, matching)")
    if block_ms is not None and len(block_ms) != len(bin_values):
        raise TypeError(f"sell_matvec: {len(block_ms)} block_ms for "
                        f"{len(bin_values)} bins")
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    outs = []
    for i, (vals, cols) in enumerate(zip(bin_values, bin_cols)):
        rb, wb = vals.shape
        if cols.shape != (rb, wb):
            raise TypeError(f"sell_matvec: bin {i} cols {cols.shape} must "
                            f"match values {vals.shape}")
        bm = min(block_ms[i] if block_ms is not None else 512, rb)
        rp = (rb + bm - 1) // bm * bm
        if rp != rb:
            # Pad ONLY the bin's rows to the tile grid (value 0 at column
            # 0, in-bounds in x) — unlike ``ell_matvec``'s recursive pad,
            # x must stay untouched: its length is n, not rows_b.
            vals = jnp.pad(vals, ((0, rp - rb), (0, 0)))
            cols = jnp.pad(cols, ((0, rp - rb), (0, 0)))
        compute_dtype, acc_dtype = _acc_dtypes(vals.dtype, x.dtype)
        out = _ell_pallas(vals, cols, x.astype(compute_dtype), bm, interpret,
                          acc_dtype, "gmres_spmv_sell")
        outs.append(out[:rb].astype(compute_dtype))
    y = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    return y[:, 0] if squeeze else y


def sell_matvec_ref(bin_values: tuple, bin_cols: tuple,
                    x: jax.Array) -> jax.Array:
    """Pure-jnp sliced-ELL SpMV oracle, sorted-row frame (see sell_matvec)."""
    if not bin_values or len(bin_values) != len(bin_cols):
        raise TypeError(f"sell_matvec_ref: {len(bin_values)} value bins vs "
                        f"{len(bin_cols)} cols bins (need >= 1, matching)")
    outs = [ell_matvec_ref(v, c, x) for v, c in zip(bin_values, bin_cols)]
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


# --------------------------------------------------------------------------
# Banded / stencil kernel
# --------------------------------------------------------------------------
# Lane-dense layout: a length-n vector travels as (n_pad / 128, 128) rows,
# so the VMEM-resident operand costs 4 bytes per row instead of the 512 a
# (n, 1) column pays once Mosaic pads its lane dim to 128.  A band offset
# ``off`` then splits into a static sublane shift ``q`` and lane shift
# ``sh`` (``divmod(front + off, 128)``): each shifted window is two
# sublane-offset loads of the resident operand joined by a lane rotation.
def _banded_kernel(bt_ref, x_ref, o_ref, *, offsets, front, br):
    i = pl.program_id(0)
    row0 = pl.multiple_of(i * br, br)        # first 128-wide row of tile
    lane = lax.broadcasted_iota(jnp.int32, (br, LANE), 1)
    for c in range(x_ref.shape[0]):          # static unroll over the k RHS
        acc = jnp.zeros((br, LANE), o_ref.dtype)
        for d, off in enumerate(offsets):    # static unroll over the bands
            q, sh = divmod(front + off, LANE)
            win = x_ref[c, pl.ds(row0 + q, br), :]
            if sh:
                # win[r, l] = x[row0+q+r, l+sh] while l+sh < 128, else the
                # next row's x[row0+q+r+1, l+sh-128]: rotate both rows left
                # by sh and take each lane from the row that holds it.
                nxt = x_ref[c, pl.ds(row0 + q + 1, br), :]
                win = jnp.where(lane < LANE - sh,
                                pltpu.roll(win, LANE - sh, 1),
                                pltpu.roll(nxt, LANE - sh, 1))
            acc += bt_ref[d].astype(o_ref.dtype) * win.astype(o_ref.dtype)
        o_ref[c] = acc


def _banded_pallas(bands, x_rows, offsets, lead, block_m, interpret,
                   acc_dtype, compute_dtype):
    """Shared pallas_call.  bands: (nbands, n); x_rows: (rows, k) holding
    local row i at ``lead + i`` (lead = 0 single-device, where rows past
    either end are zero; lead = halo for the exchanged halo operand).  The
    operand stays WHOLE in VMEM; returns the (n, k) product."""
    nbands, n = bands.shape
    k = x_rows.shape[1]
    halo = max(abs(int(o)) for o in offsets)
    sub = tuning.sublane(bands.dtype)
    br = _round_up(max(1, -(-block_m // LANE)), sub)
    br = min(br, _round_up(-(-n // LANE), sub))
    n_pad = _round_up(n, br * LANE)
    front = _round_up(halo, LANE)
    rows = _round_up(-(-(front + n_pad + halo) // LANE) + 1, 8)
    xf = jnp.pad(x_rows.T.astype(acc_dtype),
                 ((0, 0), (front - lead,
                           rows * LANE - front + lead - x_rows.shape[0])))
    bt = jnp.pad(bands, ((0, 0), (0, n_pad - n)))
    out = pl.pallas_call(
        functools.partial(_banded_kernel, offsets=offsets, front=front,
                          br=br),
        grid=(n_pad // (br * LANE),),
        in_specs=[
            pl.BlockSpec((nbands, br, LANE), lambda i: (0, i, 0)),
            pl.BlockSpec((k, rows, LANE), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((k, br, LANE), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, n_pad // LANE, LANE), acc_dtype),
        interpret=interpret,
        name="gmres_spmv_banded",
    )(bt.reshape(nbands, n_pad // LANE, LANE),
      xf.reshape(k, rows, LANE))
    return out.reshape(k, n_pad)[:, :n].T.astype(compute_dtype)


@functools.partial(jax.jit,
                   static_argnames=("offsets", "block_m", "interpret"))
def banded_matvec(bands: jax.Array, x: jax.Array, offsets: tuple, *,
                  block_m: int = 1024, interpret: bool = False) -> jax.Array:
    """y[i] = sum_d bands[d, i] * x[i + offsets[d]], out-of-range -> 0.

    bands: (nbands, n); offsets: static tuple of diagonal shifts (one per
    band, e.g. (-nx, -1, 0, 1, nx) for the five-point stencil); x: (n,) or
    (n, k).  ``block_m`` is rows per grid step, rounded up to whole
    (sublane x 128) tiles.  x is zero-padded around its resident copy so
    every shifted window is a plain slice — no gather, no per-band bounds
    check.
    """
    nbands, n = bands.shape
    if len(offsets) != nbands:
        raise TypeError(f"banded_matvec: {nbands} bands but {len(offsets)} "
                        f"offsets")
    if x.shape[0] != n:
        raise TypeError(f"banded_matvec: bands {bands.shape} @ x {x.shape} — "
                        f"x must have {n} rows")
    squeeze = x.ndim == 1
    compute_dtype, acc_dtype = _acc_dtypes(bands.dtype, x.dtype)
    out = _banded_pallas(bands, x[:, None] if squeeze else x, offsets, 0,
                         block_m, interpret, acc_dtype, compute_dtype)
    return out[:, 0] if squeeze else out


def banded_matvec_ref(bands: jax.Array, x: jax.Array,
                      offsets: tuple) -> jax.Array:
    """Pure-jnp banded SpMV oracle (and the ``kernel_mode() == "ref"`` path)."""
    halo = max(abs(int(o)) for o in offsets)
    squeeze = x.ndim == 1
    xp = x[:, None] if squeeze else x
    xp = jnp.pad(xp, ((halo, halo), (0, 0)))
    out = banded_matvec_halo_ref(bands, xp, offsets)
    return out[:, 0] if squeeze else out


# --------------------------------------------------------------------------
# Row-sharded halo variants
# --------------------------------------------------------------------------
def halo_exchange(x: jax.Array, halo: int, axis_name: str,
                  num_shards: int) -> jax.Array:
    """Fetch ``halo`` boundary rows from each mesh neighbor.

    x: the LOCAL (n_local,) or (n_local, k) shard of a row-partitioned
    vector.  Returns (n_local + 2*halo, ...) with rows [0, halo) holding
    the PREVIOUS shard's last rows and rows [halo + n_local, ...) the NEXT
    shard's first rows.  Edge shards receive zeros (``ppermute`` leaves
    non-receiving parties zeroed), which matches the kernels'
    out-of-range-reads-are-zero convention, so Dirichlet boundaries stay
    free.  Communication: 2 neighbor ppermutes of halo*k values —
    independent of the global n, vs. the (n - n_local)*k values an
    all-gather would move.

    ``num_shards`` must be the static size of ``axis_name`` (the
    permutation is built at trace time); requires halo <= n_local.
    """
    if halo == 0:
        return x
    if halo > x.shape[0]:
        raise ValueError(f"halo_exchange: halo={halo} exceeds the local "
                         f"shard length {x.shape[0]} — neighbors' neighbors "
                         f"would be needed; use an all-gather fallback")
    squeeze = x.ndim == 1
    xp = x[:, None] if squeeze else x
    down = [(p, p + 1) for p in range(num_shards - 1)]   # shard p -> p+1
    up = [(p + 1, p) for p in range(num_shards - 1)]     # shard p+1 -> p
    top = lax.ppermute(xp[-halo:], axis_name, perm=down)
    bot = lax.ppermute(xp[:halo], axis_name, perm=up)
    out = jnp.concatenate([top, xp, bot], axis=0)
    return out[:, 0] if squeeze else out


@functools.partial(jax.jit,
                   static_argnames=("offsets", "block_m", "interpret"))
def banded_matvec_halo(bands: jax.Array, x_halo: jax.Array, offsets: tuple,
                       *, block_m: int = 1024,
                       interpret: bool = False) -> jax.Array:
    """Per-shard banded SpMV over an ALREADY halo-padded operand.

    bands: the (nbands, n_local) shard of the band stack; x_halo: the
    (n_local + 2*halo, ...) output of ``halo_exchange`` (halo =
    max |offsets|).  Same kernel as ``banded_matvec`` — the only
    difference is that the halo rows hold neighbor values instead of
    zeros.  Returns the (n_local, ...) local output shard.
    """
    nbands, n = bands.shape
    if len(offsets) != nbands:
        raise TypeError(f"banded_matvec_halo: {nbands} bands but "
                        f"{len(offsets)} offsets")
    halo = max(abs(int(o)) for o in offsets)
    if x_halo.shape[0] != n + 2 * halo:
        raise TypeError(f"banded_matvec_halo: bands {bands.shape} with "
                        f"halo={halo} need x_halo of {n + 2 * halo} rows, "
                        f"got {x_halo.shape}")
    squeeze = x_halo.ndim == 1
    compute_dtype, acc_dtype = _acc_dtypes(bands.dtype, x_halo.dtype)
    out = _banded_pallas(bands, x_halo[:, None] if squeeze else x_halo,
                         offsets, halo, block_m, interpret, acc_dtype,
                         compute_dtype)
    return out[:, 0] if squeeze else out


def banded_matvec_halo_ref(bands: jax.Array, x_halo: jax.Array,
                           offsets: tuple) -> jax.Array:
    """jnp oracle / fallback for ``banded_matvec_halo`` (prepadded x)."""
    nbands, n = bands.shape
    compute_dtype, acc_dtype = _acc_dtypes(bands.dtype, x_halo.dtype)
    squeeze = x_halo.ndim == 1
    xp = x_halo[:, None] if squeeze else x_halo
    halo = max(abs(int(o)) for o in offsets)
    xp = xp.astype(acc_dtype)
    acc = jnp.zeros((n, xp.shape[1]), acc_dtype)
    for d, off in enumerate(offsets):
        seg = jax.lax.slice_in_dim(xp, halo + off, halo + off + n, axis=0)
        acc = acc + bands[d][:, None].astype(acc_dtype) * seg
    out = acc.astype(compute_dtype)
    return out[:, 0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def ell_matvec_halo(values: jax.Array, cols: jax.Array, x_halo: jax.Array,
                    *, block_m: int = 512,
                    interpret: bool = False) -> jax.Array:
    """Per-shard ELL SpMV over an ALREADY halo-padded operand.

    values/cols: the (n_local, width) shard, with ``cols`` REMAPPED to
    halo-local coordinates (global col - shard offset + halo; see
    ``SparseOperator.__call__``); x_halo: the output of ``halo_exchange``.
    The gather kernel is identical to ``ell_matvec``'s — the resident
    operand is just (n_local + 2*halo, k) instead of (n, k), which is the
    whole point: residency divides by the shard count.
    """
    n, width = values.shape
    if cols.shape != (n, width):
        raise TypeError(f"ell_matvec_halo: cols {cols.shape} must match "
                        f"values {values.shape}")
    squeeze = x_halo.ndim == 1
    if squeeze:
        x_halo = x_halo[:, None]
    bm = min(block_m, n)
    if n % bm:
        # Padding rows carry value 0 at column 0 — in-bounds in x_halo.
        np_ = (n + bm - 1) // bm * bm
        out = ell_matvec_halo(
            jnp.pad(values, ((0, np_ - n), (0, 0))),
            jnp.pad(cols, ((0, np_ - n), (0, 0))),
            x_halo, block_m=bm, interpret=interpret)[:n]
        return out[:, 0] if squeeze else out

    compute_dtype, acc_dtype = _acc_dtypes(values.dtype, x_halo.dtype)
    out = _ell_pallas(values, cols, x_halo.astype(compute_dtype), bm,
                      interpret, acc_dtype,
                      "gmres_spmv_ell_halo").astype(compute_dtype)
    return out[:, 0] if squeeze else out
