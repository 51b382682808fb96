"""Pallas TPU kernels: the s-step matrix-powers computation in ONE launch.

The s-step (communication-avoiding) GMRES cycle (core/sstep.py) opens each
block with s normalized mat-vec powers

    u_0 = v_k;   w = A u_{j-1};  sigma_j = ||w||;  u_j = w / sigma_j

and NO per-step inner products.  Run through the operator layer that is s
separate SpMV/GEMV launches: each power streams A from HBM, writes w back,
re-reads it for the norm, and writes the normalized u — the intermediate
vectors round-trip s times even though every u_j is consumed exactly once,
by the very next power.

These kernels run the WHOLE power sequence in one ``pallas_call``:

``banded_powers`` — banded/stencil operators.  The band stack (nbands, n)
  is tiny next to a dense matrix (5 vectors for the five-point Poisson
  stencil), so it sits ENTIRELY in VMEM together with the operand and the
  (s, n) output block: A is read from HBM exactly ONCE for all s powers
  (s HBM passes collapse to 1) and no u_j ever exists in HBM before the
  final block write.  The grid is (s,) — one step per power — with the
  current operand carried in a halo-padded VMEM scratch between steps, so
  each power is pure VPU work over statically shifted windows (the same
  gather-free structure as ``spmv.banded_matvec``).

``dense_powers`` — explicit dense A.  The (n, n) matrix cannot be
  VMEM-resident, so A streams once PER POWER in MXU-aligned (b, b) tiles
  (grid (s, nbi, nbj), tile index maps ignore the power index) — that
  stream is irreducible for dense A (see core/sstep.py's round-count
  analysis).  What fusion removes is everything else: the w accumulator
  and the current operand live in VMEM scratch across the whole grid, the
  normalization reductions run in-register at each power boundary, and
  only the final (s, n) block + sigmas are written out.

Both kernels accumulate in f32 (f64 under x64) whatever the storage dtype
— bf16 bands/tiles halve the matrix stream without quantizing the power
recurrence — and both bake the breakdown guard ``u = w / max(|w|, guard)``
with ``guard = tiny**0.5`` (the standard solver's normalization guard:
small enough that any representable system scale keeps the recurrence
``A u_{j-1} = sigma_j u_j`` exact, only a true zero block is clamped), so
a collapsed basis (solve converged mid-block) degrades exactly like the
jnp reference.

``banded_powers_halo`` (PR 5) — the ROW-SHARDED banded variant, i.e. the
  classic communication-avoiding matrix-powers kernel (Demmel/Hoemmen
  line, which Chronopoulos' s-step method anticipates): ONE ``ppermute``
  halo exchange of width s*halo brings in every remote operand value the
  whole s-power sequence will touch, the per-shard kernel then computes
  the s UNNORMALIZED powers z_j = A^j u_0 over the shrinking-validity
  halo-padded shard (wrongness creeps inward one halo per power and never
  reaches the center rows), and ONE psum afterwards completes all s
  squared norms at once — from which u_j = z_j/||z_j|| and
  sigma_j = ||z_j||/||z_{j-1}|| are recovered exactly.  Collective
  rounds per block: 2 (one neighbor exchange + one psum) vs the
  reference's s all-gathers + s psums.  The deferred normalization costs
  dynamic range — |z_s| grows like ||A||^s — so the CALLER must pre-scale
  the band stack by theta >= ||A|| and multiply theta back into the
  sigmas (core/sstep.py does exactly this with the pmax-completed
  ||A||_inf row-sum bound, making the path overflow-proof and
  scale-invariant at any system scale; the residual conditioning left is
  the monomial basis's own kappa^s, which bounds practical s at ~8
  regardless of implementation).

``matrix_powers_ref`` is the jnp oracle and the ``kernel_mode() == "ref"``
fallback (also the dense row-sharded path: dense A needs the whole
operand per power, so an all-gather per power is irreducible there): the
per-power norm psums over ``axis_name``.

HBM traffic per s-step block (f32, five-point stencil, modeled in
``benchmarks/kernel_bench.py`` as the ``sstep_powers_*`` rows):

    fused banded:  (nbands + s + 1) * 4n         bands + x in, U out
    s SpMV launches: s * (nbands + 4) * 4n       bands re-streamed + w/u trips

— ratio (nbands + s + 1) / (s * (nbands + 4)) ~= 0.28 at s = 4.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning


def _acc_dtype(mat_dtype, x_dtype):
    return jnp.promote_types(jnp.promote_types(mat_dtype, x_dtype),
                             jnp.float32)


def _lane_put(row, p, value):
    """``row`` (1, L) with lane ``p`` set to ``value``.  Mosaic stores no
    scalar to VMEM and no dynamic lane slice, so the write is a masked
    select over the whole row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.where(lane == p, value, row)


def _lane_pick(row, p):
    """Lane ``p`` of ``row`` (1, L) as a (1, 1) value (see ``_lane_put``)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == p, row, 0), axis=1, keepdims=True)


# --------------------------------------------------------------------------
# Banded / stencil matrix powers
# --------------------------------------------------------------------------
def _banded_powers_kernel(bands_ref, x_ref, sh_ref, u_ref, sig_ref,
                          pad_ref, *, offsets, halo, eps, shifted):
    p = pl.program_id(0)
    n_pad = u_ref.shape[1]
    acc = sig_ref.dtype

    @pl.when(p == 0)
    def _seed():
        # Zero the halo once; the operand for power 0 is x itself.
        pad_ref[...] = jnp.zeros_like(pad_ref)
        pad_ref[:, pl.ds(halo, n_pad)] = x_ref[...].astype(acc)

    # One banded mat-vec over the VMEM-carried operand: static unroll over
    # the diagonals, each band an elementwise product with a shifted window
    # of the halo-padded current vector.  Padded columns (>= n) carry zero
    # bands, so they contribute nothing to w or the norm.
    w = jnp.zeros((1, n_pad), acc)
    for d, off in enumerate(offsets):
        band = bands_ref[d:d + 1, :].astype(acc)              # (1, n_pad)
        w += band * pad_ref[:, pl.ds(halo + off, n_pad)]
    if shifted:
        # Newton basis: w = (A - shift_j I) u_{j-1} — same one-pass stream,
        # the shift is a per-power scalar from the tiny shifts block.
        sh = _lane_pick(sh_ref[...], p)
        w -= sh * pad_ref[:, pl.ds(halo, n_pad)]

    sigma = jnp.sqrt(jnp.sum(w * w))
    u = w / jnp.maximum(sigma, eps)
    sig_ref[...] = _lane_put(sig_ref[...], p, sigma)
    u_ref[pl.ds(p, 1), :] = u
    pad_ref[:, pl.ds(halo, n_pad)] = u     # operand for the next power


@functools.partial(jax.jit,
                   static_argnames=("offsets", "s", "interpret"))
def banded_powers(bands: jax.Array, x: jax.Array, offsets: tuple, s: int, *,
                  shifts: jax.Array | None = None,
                  interpret: bool = False):
    """All s normalized powers of a banded operator in one launch.

    bands: (nbands, n); offsets: static diagonal shifts (see
    ``spmv.banded_matvec``); x: (n,) starting vector (u_0).  Returns
    ``(u, sigma)`` with u (s, n) — row j-1 is u_j — and sigma (s,), the
    pre-normalization norms.  With ``shifts`` (s,) the recurrence is the
    NEWTON basis ``w = (A - shifts[j] I) u_{j-1}`` (shifts at Chebyshev
    points of the spectral interval keep the basis conditioned far past
    the monomial kappa^s wall — see core/sstep.py); the Hessenberg
    relation becomes ``A u_{j-1} = sigma_j u_j + shifts[j] u_{j-1}``.
    """
    nbands, n = bands.shape
    if len(offsets) != nbands:
        raise TypeError(f"banded_powers: {nbands} bands but {len(offsets)} "
                        f"offsets")
    if x.shape != (n,):
        raise TypeError(f"banded_powers: bands {bands.shape} need x of "
                        f"shape ({n},), got {x.shape}")
    halo = max(abs(int(o)) for o in offsets)
    n_pad = tuning._round_up(n, tuning.LANE)
    acc = _acc_dtype(bands.dtype, x.dtype)
    eps = float(jnp.finfo(acc).tiny) ** 0.5   # breakdown guard, scale-free
    if n_pad != n:
        bands = jnp.pad(bands, ((0, 0), (0, n_pad - n)))
        x = jnp.pad(x, (0, n_pad - n))
    s_pad = tuning._round_up(s, tuning.sublane(acc))
    shifted = shifts is not None
    sh = (jnp.zeros(s, acc) if shifts is None
          else jnp.asarray(shifts, acc).reshape(s))
    sh = jnp.pad(sh, (0, s_pad - s))[None, :]

    u, sig = pl.pallas_call(
        functools.partial(_banded_powers_kernel, offsets=offsets,
                          halo=halo, eps=eps, shifted=shifted),
        grid=(s,),
        in_specs=[
            # Both operands are ONE block each: fetched once, VMEM-resident
            # across all s powers.
            pl.BlockSpec((nbands, n_pad), lambda p: (0, 0)),
            pl.BlockSpec((1, n_pad), lambda p: (0, 0)),
            pl.BlockSpec((1, s_pad), lambda p: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((s_pad, n_pad), lambda p: (0, 0)),
            pl.BlockSpec((1, s_pad), lambda p: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, n_pad), acc),
            jax.ShapeDtypeStruct((1, s_pad), acc),
        ],
        scratch_shapes=[pltpu.VMEM((1, n_pad + 2 * halo), acc)],
        interpret=interpret,
        name="gmres_sstep_powers_banded",
    )(bands, x[None, :], sh)
    return u[:s, :n], sig[0, :s]


# --------------------------------------------------------------------------
# Row-sharded banded matrix powers (communication-avoiding)
# --------------------------------------------------------------------------
def _banded_powers_halo_kernel(bands_ref, x_ref, z_ref, nrm_ref, pad_ref, *,
                               offsets, halo, center, ln):
    p = pl.program_id(0)
    w_width = x_ref.shape[1]                 # n_local + 2*s*halo
    acc = nrm_ref.dtype

    @pl.when(p == 0)
    def _seed():
        pad_ref[...] = jnp.zeros_like(pad_ref)
        pad_ref[:, pl.ds(halo, w_width)] = x_ref[...].astype(acc)

    # One UNNORMALIZED banded mat-vec over the whole halo-padded width.
    # Positions closer than p*halo to either edge go stale (their true
    # neighbors were not exchanged) — by construction the center slice
    # stays exact through all s powers (see module docstring).
    w = jnp.zeros((1, w_width), acc)
    for d, off in enumerate(offsets):
        band = bands_ref[d:d + 1, :].astype(acc)
        w += band * pad_ref[:, pl.ds(halo + off, w_width)]

    zc = w[:, center:center + ln]            # this shard's rows of z_{p+1}
    nrm_ref[...] = _lane_put(nrm_ref[...], p,       # PER-SHARD partial
                             jnp.sum(zc * zc))      # sq-norm
    z_ref[pl.ds(p, 1), :] = zc
    pad_ref[:, pl.ds(halo, w_width)] = w     # raw carry — no division here


@functools.partial(jax.jit, static_argnames=("offsets", "s", "interpret"))
def banded_powers_halo(bands_pad: jax.Array, x_halo: jax.Array,
                       offsets: tuple, s: int, *, interpret: bool = False):
    """All s RAW powers of a row-sharded banded operator in one launch.

    bands_pad: (nbands, n_local + 2*s*halo) — the local band-stack shard
    extended with (s-1)*halo exchanged neighbor columns each side and then
    halo zeros each side (the caller builds this ONCE per solve; bands are
    loop-invariant).  x_halo: (n_local + 2*s*halo,) — ``halo_exchange`` of
    the unit-norm starting vector with width s*halo.  Returns
    ``(z, nrm_partial)``: z (s, n_local) holds the LOCAL rows of the raw
    powers z_j = A^j u_0, and nrm_partial (s,) their per-shard squared
    norms — one psum of nrm_partial recovers every ||z_j||, from which
    u_j = z_j / ||z_j|| and sigma_j = ||z_j|| / ||z_{j-1}|| follow with
    NO collective between powers.
    """
    nbands, w_width = bands_pad.shape
    if len(offsets) != nbands:
        raise TypeError(f"banded_powers_halo: {nbands} bands but "
                        f"{len(offsets)} offsets")
    halo = max(abs(int(o)) for o in offsets)
    ln = w_width - 2 * s * halo
    if ln <= 0:
        raise TypeError(f"banded_powers_halo: padded width {w_width} too "
                        f"small for s={s} powers of halo={halo}")
    if x_halo.shape != (w_width,):
        raise TypeError(f"banded_powers_halo: bands_pad {bands_pad.shape} "
                        f"needs x_halo of shape ({w_width},), got "
                        f"{x_halo.shape}")
    acc = _acc_dtype(bands_pad.dtype, x_halo.dtype)
    s_pad = tuning._round_up(s, tuning.sublane(acc))

    z, nrm = pl.pallas_call(
        functools.partial(_banded_powers_halo_kernel, offsets=offsets,
                          halo=halo, center=s * halo, ln=ln),
        grid=(s,),
        in_specs=[
            # Band stack and operand are ONE VMEM-resident block each; per
            # shard that is 1/P of the global residency, which is how the
            # sharded fits-check admits systems the single-device kernel
            # cannot hold.
            pl.BlockSpec((nbands, w_width), lambda p: (0, 0)),
            pl.BlockSpec((1, w_width), lambda p: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((s_pad, ln), lambda p: (0, 0)),
            pl.BlockSpec((1, s_pad), lambda p: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, ln), acc),
            jax.ShapeDtypeStruct((1, s_pad), acc),
        ],
        scratch_shapes=[pltpu.VMEM((1, w_width + 2 * halo), acc)],
        interpret=interpret,
        name="gmres_sstep_powers_banded_halo",
    )(bands_pad, x_halo[None, :])
    return z[:s, :], nrm[0, :s]


# --------------------------------------------------------------------------
# Dense matrix powers
# --------------------------------------------------------------------------
def _dense_powers_kernel(a_ref, x_ref, u_ref, sig_ref, cur_ref, w_ref, *,
                         bm, s, nb, eps):
    p = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    acc = sig_ref.dtype
    first_tile = (i == 0) & (j == 0)

    def _normalize(power):
        w = w_ref[...]
        sigma = jnp.sqrt(jnp.sum(w * w))
        sig_ref[...] = _lane_put(sig_ref[...], power, sigma)
        u = w / jnp.maximum(sigma, eps)
        u_ref[pl.ds(power, 1), :] = u
        return u

    @pl.when(first_tile & (p == 0))
    def _seed():
        cur_ref[...] = x_ref[...].astype(acc)

    @pl.when(first_tile & (p > 0))
    def _advance():
        # Fused normalization: the finished power's norm and scale run
        # in-register at the power boundary — w never visits HBM.
        cur_ref[...] = _normalize(p - 1)

    @pl.when(first_tile)
    def _reset():
        w_ref[...] = jnp.zeros_like(w_ref)

    # w[i-block] += cur[j-block] @ A[i, j]^T — row-major throughout so the
    # per-tile partial lands directly in the (1, n) accumulator.
    w_ref[:, pl.ds(i * bm, bm)] += jax.lax.dot_general(
        cur_ref[:, pl.ds(j * bm, bm)], a_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=acc,
    )

    @pl.when((p == s - 1) & (i == nb - 1) & (j == nb - 1))
    def _finish():
        _normalize(s - 1)


@functools.partial(jax.jit, static_argnames=("s", "block", "interpret"))
def dense_powers(a: jax.Array, x: jax.Array, s: int, *,
                 block: int | None = None, interpret: bool = False):
    """All s normalized powers of a dense A in one launch.

    a: (n, n); x: (n,).  A streams once per power (irreducible for dense
    storage); the w accumulator, current operand, and all s normalization
    reductions stay in VMEM.  Returns ``(u, sigma)`` as ``banded_powers``.
    """
    n = a.shape[0]
    if a.shape != (n, n) or x.shape != (n,):
        raise TypeError(f"dense_powers: a {a.shape} must be square and x "
                        f"{x.shape} of length {n}")
    if block is None:
        block = tuning.choose_powers_block(n, jnp.dtype(a.dtype).name, s=s)
    b = min(block, tuning._round_up(n, tuning.LANE))
    n_pad = tuning._round_up(n, b)
    acc = _acc_dtype(a.dtype, x.dtype)
    eps = float(jnp.finfo(acc).tiny) ** 0.5   # breakdown guard, scale-free
    if n_pad != n:
        a = jnp.pad(a, ((0, n_pad - n), (0, n_pad - n)))
        x = jnp.pad(x, (0, n_pad - n))
    nb = n_pad // b
    s_pad = tuning._round_up(s, tuning.sublane(acc))

    u, sig = pl.pallas_call(
        functools.partial(_dense_powers_kernel, bm=b, s=s, nb=nb, eps=eps),
        grid=(s, nb, nb),
        in_specs=[
            # A tiles ignore the power index: the same (i, j) sweep streams
            # the matrix once per power.
            pl.BlockSpec((b, b), lambda p, i, j: (i, j)),
            pl.BlockSpec((1, n_pad), lambda p, i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((s_pad, n_pad), lambda p, i, j: (0, 0)),
            pl.BlockSpec((1, s_pad), lambda p, i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, n_pad), acc),
            jax.ShapeDtypeStruct((1, s_pad), acc),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, n_pad), acc),      # current operand u_{j-1}
            pltpu.VMEM((1, n_pad), acc),      # w accumulator
        ],
        interpret=interpret,
        name="gmres_sstep_powers_dense",
    )(a, x[None, :])
    return u[:s, :n], sig[0, :s]


# --------------------------------------------------------------------------
# ELL matrix powers (general sparsity)
# --------------------------------------------------------------------------
def _ell_powers_kernel(vals_ref, cols_ref, x_ref, sh_ref, u_ref, sig_ref,
                       cur_ref, *, eps, shifted):
    p = pl.program_id(0)
    acc = sig_ref.dtype

    @pl.when(p == 0)
    def _seed():
        cur_ref[...] = x_ref[...].astype(acc)

    # One gather-style SpMV over the VMEM-carried operand (same structure
    # as ``spmv._ell_kernel``, minus the row tiling: values/cols stay whole
    # so the sparse column pattern can reach any operand row).  Padding
    # slots carry value 0 at column 0, contributing nothing.
    g = jnp.take(cur_ref[0, :], cols_ref[...], axis=0).astype(acc)
    w = jnp.sum(vals_ref[...].astype(acc) * g, axis=1)[None, :]
    if shifted:
        sh = _lane_pick(sh_ref[...], p)
        w -= sh * cur_ref[...]

    sigma = jnp.sqrt(jnp.sum(w * w))
    u = w / jnp.maximum(sigma, eps)
    sig_ref[...] = _lane_put(sig_ref[...], p, sigma)
    u_ref[pl.ds(p, 1), :] = u
    cur_ref[...] = u                         # operand for the next power


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def ell_powers(values: jax.Array, cols: jax.Array, x: jax.Array, s: int, *,
               shifts: jax.Array | None = None, interpret: bool = False):
    """All s normalized powers of an ELL-format operator in one launch.

    values/cols: (n, width) as in ``spmv.ell_matvec``; x: (n,).  The
    values+cols pair is fetched ONCE and stays VMEM-resident across all s
    powers (gated by ``tuning.ell_powers_fits``), closing the general-
    sparsity gap in the s-step cycle: previously only banded operators
    took the fused-powers path.  ``shifts`` selects the Newton basis as in
    ``banded_powers``.  Returns ``(u, sigma)``.
    """
    n, width = values.shape
    if cols.shape != (n, width):
        raise TypeError(f"ell_powers: cols {cols.shape} must match values "
                        f"{values.shape}")
    if x.shape != (n,):
        raise TypeError(f"ell_powers: values {values.shape} need x of "
                        f"shape ({n},), got {x.shape}")
    n_pad = tuning._round_up(n, tuning.LANE)
    acc = _acc_dtype(values.dtype, x.dtype)
    eps = float(jnp.finfo(acc).tiny) ** 0.5
    if n_pad != n:
        values = jnp.pad(values, ((0, n_pad - n), (0, 0)))
        cols = jnp.pad(cols, ((0, n_pad - n), (0, 0)))
        x = jnp.pad(x, (0, n_pad - n))
    s_pad = tuning._round_up(s, tuning.sublane(acc))
    shifted = shifts is not None
    sh = (jnp.zeros(s, acc) if shifts is None
          else jnp.asarray(shifts, acc).reshape(s))
    sh = jnp.pad(sh, (0, s_pad - s))[None, :]

    u, sig = pl.pallas_call(
        functools.partial(_ell_powers_kernel, eps=eps, shifted=shifted),
        grid=(s,),
        in_specs=[
            pl.BlockSpec((n_pad, width), lambda p: (0, 0)),
            pl.BlockSpec((n_pad, width), lambda p: (0, 0)),
            pl.BlockSpec((1, n_pad), lambda p: (0, 0)),
            pl.BlockSpec((1, s_pad), lambda p: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((s_pad, n_pad), lambda p: (0, 0)),
            pl.BlockSpec((1, s_pad), lambda p: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, n_pad), acc),
            jax.ShapeDtypeStruct((1, s_pad), acc),
        ],
        scratch_shapes=[pltpu.VMEM((1, n_pad), acc)],
        interpret=interpret,
        name="gmres_sstep_powers_ell",
    )(values, cols, x[None, :], sh)
    return u[:s, :n], sig[0, :s]


# --------------------------------------------------------------------------
# Fused Chebyshev preconditioner apply
# --------------------------------------------------------------------------
def _banded_cheb_kernel(bands_ref, v_ref, o_ref, zp_ref, *,
                        offsets, halo, theta, delta, rhos):
    acc = o_ref.dtype
    n_pad = o_ref.shape[1]
    zp_ref[...] = jnp.zeros_like(zp_ref)     # zero the halo once
    v = v_ref[...].astype(acc)
    z = v / theta
    z_old = jnp.zeros_like(v)
    # The whole three-term recurrence unrolls STATICALLY — theta/delta/rhos
    # are Python floats baked at trace time — so the band stack is read
    # from HBM exactly once for all `order` mat-vecs and no intermediate z
    # ever exists outside VMEM.
    for rho, rho_old in rhos:
        zp_ref[:, pl.ds(halo, n_pad)] = z
        w = jnp.zeros((1, n_pad), acc)
        for d, off in enumerate(offsets):
            band = bands_ref[d:d + 1, :].astype(acc)
            w += band * zp_ref[:, pl.ds(halo + off, n_pad)]
        z_new = rho * (2.0 / delta * (v - w) + rho_old * (z - z_old)) + z
        z_old, z = z, z_new
    o_ref[...] = z


@functools.partial(jax.jit, static_argnames=("offsets", "theta", "delta",
                                             "rhos", "interpret"))
def banded_cheb_apply(bands: jax.Array, v: jax.Array, offsets: tuple, *,
                      theta: float, delta: float, rhos: tuple,
                      interpret: bool = False) -> jax.Array:
    """z ~= A^{-1} v by the fused Chebyshev recurrence (one launch).

    bands/offsets as in ``spmv.banded_matvec``; theta/delta/rhos from
    ``core/preconditioners.cheb_coeffs`` (static Python floats — the
    spectral interval is estimated once at setup).  This is the kernel
    behind ``ChebyshevPreconditioner`` on single-shard banded operators:
    len(rhos) mat-vecs for ONE HBM pass over the band stack, gated by
    ``tuning.cheb_fits``.
    """
    nbands, n = bands.shape
    if len(offsets) != nbands:
        raise TypeError(f"banded_cheb_apply: {nbands} bands but "
                        f"{len(offsets)} offsets")
    if v.shape != (n,):
        raise TypeError(f"banded_cheb_apply: bands {bands.shape} need v of "
                        f"shape ({n},), got {v.shape}")
    halo = max(abs(int(o)) for o in offsets)
    n_pad = tuning._round_up(n, tuning.LANE)
    acc = _acc_dtype(bands.dtype, v.dtype)
    out_dtype = jnp.promote_types(bands.dtype, v.dtype)
    if n_pad != n:
        bands = jnp.pad(bands, ((0, 0), (0, n_pad - n)))
        v = jnp.pad(v, (0, n_pad - n))

    z = pl.pallas_call(
        functools.partial(_banded_cheb_kernel, offsets=offsets, halo=halo,
                          theta=float(theta), delta=float(delta),
                          rhos=tuple(rhos)),
        in_specs=[
            pl.BlockSpec((nbands, n_pad), lambda: (0, 0)),
            pl.BlockSpec((1, n_pad), lambda: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_pad), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), acc),
        scratch_shapes=[pltpu.VMEM((1, n_pad + 2 * halo), acc)],
        interpret=interpret,
        name="gmres_precond_cheb_fused",
    )(bands, v[None, :])
    return z[0, :n].astype(out_dtype)


# --------------------------------------------------------------------------
# jnp oracle / fallback
# --------------------------------------------------------------------------
def matrix_powers_ref(matvec, x: jax.Array, s: int, eps, axis_name=None,
                      shifts: jax.Array | None = None):
    """s normalized powers via s sequential mat-vecs (the jnp reference).

    ``matvec`` is any operator/callable; under ``axis_name`` the per-power
    norm psums over the mesh axis — the reason the row-sharded s-step solve
    stays on this path (the reduction must cross shards between powers).
    ``shifts`` (s,) selects the Newton basis as in ``banded_powers``.
    """
    from jax import lax

    def power(u, shift):
        w = matvec(u)
        if shift is not None:
            w = w - shift * u
        nrm2 = jnp.vdot(w, w).real
        if axis_name is not None:
            nrm2 = lax.psum(nrm2, axis_name)
        sigma = jnp.sqrt(nrm2)
        u_next = w / jnp.maximum(sigma, jnp.asarray(eps, w.dtype))
        return u_next, (u_next, sigma)

    xs = None if shifts is None else jnp.asarray(shifts).reshape(s)
    _, (u, sigma) = lax.scan(power, x, xs, length=s)
    return u, sigma
