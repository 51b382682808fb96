"""Pallas TPU kernels: banded ILU(0) factorization + triangular sweeps.

This is the kernel layer behind ``core/preconditioners.BandedILU0`` (and
its ``line_jacobi`` / ``banded_block_jacobi`` restrictions).  Two pieces:

``banded_ilu0(bands, offsets)`` — the SETUP.  Incomplete LU restricted to
  the band pattern: a single ``lax.scan`` over rows carrying a ring buffer
  of the last K factored rows (K = number of subdiagonals = -min(offsets)),
  so setup is one streaming pass, O(n * nbands^2) flops and O(K * nbands)
  live state — the "O(bands) setup" a stencil operator deserves, vs the
  O(n^3) dense LU that ``block_jacobi`` pays.  All inter-row offset
  combinatorics are resolved in PYTHON (the offsets tuple is static), so
  the scan body is pure static-indexed arithmetic; band entries whose
  column falls outside [0, n) are masked to zero first (BandedOperator
  storage does not guarantee zeros there), and the pivot gets a
  scale-relative safe replacement AT FACTOR TIME so the sweeps below never
  need an in-kernel guard.

``banded_trisweep(bands, v, offsets, unit_diag=, lower=)`` — the APPLY.
  Solves the banded triangular system (unit-lower forward substitution or
  upper backward substitution).  Dispatch follows the standard kernel
  policy (``tuning.kernel_mode`` x ``tuning.trisweep_fits``): the Pallas
  kernel walks sequential row blocks on a (nb,) grid with the trailing K
  solved entries carried in a VMEM scratch ring — one HBM read of bands/v
  and one write of z — and ``banded_trisweep_ref`` is the psum-safe
  blocked jnp oracle (also the vmapped multi-RHS path: substitution is
  sequential in rows but embarrassingly parallel across lanes).

  An UPPER solve is a lower solve read back-to-front: flip bands/v along
  the row axis, negate the offsets, forward-substitute, flip the result.
  Both directions therefore share one kernel and one reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning
from repro.kernels.tuning import _round_up


def _mask_oob(bands, offsets):
    """Zero band entries whose column i + off falls outside [0, n)."""
    n = bands.shape[1]
    rows = jnp.arange(n)
    masked = []
    for d, off in enumerate(offsets):
        cols = rows + off
        masked.append(jnp.where((cols >= 0) & (cols < n), bands[d], 0))
    return jnp.stack(masked)


def banded_ilu0(bands: jax.Array, offsets: tuple):
    """ILU(0) of a banded matrix, restricted to its own band pattern.

    bands: (nbands, n) with ``a[i, i+off_d] = bands[d, i]``; offsets must
    include 0.  Returns ``(l_bands, l_offsets, u_bands, u_offsets)``:
    the strictly-lower factor (unit diagonal implied) and the upper factor
    (diagonal included), both in the same DIA layout, ready for
    ``banded_trisweep``.
    """
    offsets = tuple(int(o) for o in offsets)
    nbands = bands.shape[0]
    if len(offsets) != nbands:
        raise TypeError(f"banded_ilu0: {nbands} bands but {len(offsets)} "
                        f"offsets")
    if 0 not in offsets:
        raise ValueError("banded_ilu0: offsets must include the diagonal "
                         "(offset 0)")
    l_offsets = tuple(sorted(o for o in offsets if o < 0))
    u_offsets = tuple([0] + sorted(o for o in offsets if o > 0))
    l_bands, u_bands = _ilu0_factor(bands, offsets)
    return l_bands, l_offsets, u_bands, u_offsets


@functools.partial(jax.jit, static_argnames=("offsets",))
def _ilu0_factor(bands: jax.Array, offsets: tuple):
    n = bands.shape[1]
    idx = {off: d for d, off in enumerate(offsets)}
    lower = sorted(o for o in offsets if o < 0)    # most negative first
    upper = sorted(o for o in offsets if o > 0)
    k_ring = -lower[0] if lower else 1

    acc = jnp.promote_types(bands.dtype, jnp.float32)
    a = _mask_oob(bands.astype(acc), offsets)
    eps = jnp.finfo(acc).eps
    tiny = jnp.finfo(acc).tiny

    def step(ring, a_row):
        # ring: (k_ring, nbands) — ring[k_ring + l] is factored row i + l.
        row = a_row
        for l in lower:
            krow = ring[k_ring + l]
            lik = row[idx[l]] / krow[idx[0]]
            row = row.at[idx[l]].set(lik)
            # Row k's U entries sit at columns k + off_u; in row i's frame
            # that is offset off_u + l — update only where the pattern has
            # a slot (that IS the ILU(0) restriction).
            for off_u in upper:
                tgt = off_u + l
                if tgt in idx:
                    row = row.at[idx[tgt]].add(-lik * krow[idx[off_u]])
        # Scale-relative safe pivot: a (near-)zero diagonal after
        # elimination would poison every later row through the ring, so
        # replace it HERE — the sweeps then divide unconditionally.
        piv = row[idx[0]]
        floor = jnp.maximum(jnp.max(jnp.abs(row)) * eps, tiny ** 0.5)
        sgn = jnp.where(piv < 0, -1.0, 1.0).astype(acc)
        row = row.at[idx[0]].set(
            jnp.where(jnp.abs(piv) >= floor, piv, sgn * floor))
        ring = jnp.concatenate([ring[1:], row[None]])
        return ring, row

    # Seed with unit diagonals so the first rows' (masked-to-zero) lower
    # entries divide by 1 instead of garbage.
    nbands = len(offsets)
    seed = jnp.zeros((k_ring, nbands), acc).at[:, idx[0]].set(1.0)
    _, fact = lax.scan(step, seed, a.T)            # fact: (n, nbands)
    fact = fact.T

    l_bands = (jnp.stack([fact[idx[o]] for o in lower])
               if lower else jnp.zeros((0, n), acc))
    u_bands = jnp.stack([fact[idx[o]] for o in [0] + upper])
    return l_bands, u_bands


# --------------------------------------------------------------------------
# Triangular sweep: blocked jnp reference
# --------------------------------------------------------------------------
def _forward_ref(bands, v, offsets, unit_diag):
    """Forward substitution; offsets all <= 0 (0 present iff not unit).

    Rows go in blocks of L = the smallest |offset| other than 1, so every
    other offset reaches only rows of earlier blocks and a block's rows
    couple to each other through offset -1 alone.  Per block the far
    terms are one vector update and the -1 chain ``z_i = a_i z_(i-1) +
    w_i`` one ``associative_scan`` of affine maps: n / L sequential steps
    (the grid height for a 2-D stencil) instead of n, which is what makes
    this path usable on the chip.
    """
    acc = jnp.promote_types(bands.dtype if bands.size else v.dtype,
                            jnp.promote_types(v.dtype, jnp.float32))
    n = v.shape[0]
    idx = {off: d for d, off in enumerate(offsets)}
    far = [o for o in offsets if o < -1]
    k = max((-o for o in offsets), default=0)       # deepest reach back
    blk = min(min((-o for o in far), default=n), n) or 1
    nblk = -(-n // blk)
    n_pad = nblk * blk

    def band(off, fill):
        b = bands[idx[off]].astype(acc)
        return jnp.pad(b, (0, n_pad - n), constant_values=fill)

    v_p = jnp.pad(v.astype(acc), (0, n_pad - n))
    sub = band(-1, 0.0) if -1 in idx else jnp.zeros((n_pad,), acc)
    diag = None if unit_diag else band(0, 1.0)
    far_bands = [(off, band(off, 0.0)) for off in far]

    def block(t, z):
        # z holds row i at k + i; rows before the first stay zero.
        start = t * blk
        w = lax.dynamic_slice(v_p, (start,), (blk,))
        for off, b in far_bands:
            w = w - (lax.dynamic_slice(b, (start,), (blk,))
                     * lax.dynamic_slice(z, (k + start + off,), (blk,)))
        a = -lax.dynamic_slice(sub, (start,), (blk,))
        if diag is not None:
            d = lax.dynamic_slice(diag, (start,), (blk,))
            a, w = a / d, w / d
        # Compose z -> a_i z + w_i left to right: prefix map (p_i, q_i).
        p, q = lax.associative_scan(
            lambda e, l: (l[0] * e[0], l[0] * e[1] + l[1]), (a, w))
        z_in = lax.dynamic_index_in_dim(z, k + start - 1, keepdims=False)
        return lax.dynamic_update_slice(z, p * z_in + q, (k + start,))

    z = lax.fori_loop(0, nblk, block, jnp.zeros((k + n_pad,), acc))
    return z[k:k + n].astype(jnp.promote_types(bands.dtype, v.dtype))


def banded_trisweep_ref(bands: jax.Array, v: jax.Array, offsets: tuple, *,
                        unit_diag: bool, lower: bool) -> jax.Array:
    """Pure-jnp triangular sweep oracle (and the ``"ref"``-mode path)."""
    offsets = tuple(int(o) for o in offsets)
    _check_tri(bands, v, offsets, unit_diag, lower)
    if lower:
        return _forward_ref(bands, v, offsets, unit_diag)
    # Upper solve == lower solve of the row-reversed system.
    flip = _forward_ref(bands[:, ::-1] if bands.size else bands, v[::-1],
                        tuple(-o for o in offsets), unit_diag)
    return flip[::-1]


def _check_tri(bands, v, offsets, unit_diag, lower):
    if bands.shape[0] != len(offsets):
        raise TypeError(f"banded_trisweep: {bands.shape[0]} bands but "
                        f"{len(offsets)} offsets")
    if bands.size and bands.shape[1] != v.shape[0]:
        raise TypeError(f"banded_trisweep: bands {bands.shape} vs "
                        f"v {v.shape}")
    bad = [o for o in offsets if (o > 0 if lower else o < 0)]
    if bad:
        side = "lower" if lower else "upper"
        raise ValueError(f"banded_trisweep: offsets {bad} on the wrong "
                         f"side for a {side} sweep")
    if not unit_diag and 0 not in offsets:
        raise ValueError("banded_trisweep: unit_diag=False needs the "
                         "diagonal band (offset 0)")


# --------------------------------------------------------------------------
# Triangular sweep: Pallas kernel
# --------------------------------------------------------------------------
def _trisweep_kernel(b_ref, v_ref, o_ref, zp_ref, *,
                     offsets, unit_diag, k_ring, bm):
    """Sequential row blocks, one matrix row per SUBLANE: b_ref is the
    (bm, nbands) tile, v_ref/o_ref (bm, 1), and zp_ref (k_ring + bm, 1)
    carries the trailing k_ring solved entries across blocks (requires
    bm >= k_ring).  Rows on sublanes make every per-row access a dynamic
    sublane offset, which Mosaic lowers; a dynamic LANE offset it refuses.
    """
    i = pl.program_id(0)
    acc = o_ref.dtype
    idx0 = offsets.index(0) if 0 in offsets else None

    @pl.when(i == 0)
    def _seed():
        zp_ref[...] = jnp.zeros_like(zp_ref)

    def row(r, carry):
        z = v_ref[pl.ds(r, 1), :].astype(acc)
        for d, off in enumerate(offsets):
            if off < 0:
                coef = b_ref[pl.ds(r, 1), pl.ds(d, 1)].astype(acc)
                z = z - coef * zp_ref[pl.ds(r + k_ring + off, 1), :]
        if not unit_diag:
            z = z / b_ref[pl.ds(r, 1), pl.ds(idx0, 1)].astype(acc)
        zp_ref[pl.ds(k_ring + r, 1), :] = z
        o_ref[pl.ds(r, 1), :] = z
        return carry

    lax.fori_loop(0, bm, row, 0, unroll=False)
    # Shift the trailing solved entries to the front for the next block.
    zp_ref[:k_ring, :] = zp_ref[bm:bm + k_ring, :]


@functools.partial(
    jax.jit, static_argnames=("offsets", "unit_diag", "lower", "block_m",
                              "interpret"))
def banded_trisweep_kernel(bands: jax.Array, v: jax.Array, offsets: tuple, *,
                           unit_diag: bool, lower: bool,
                           block_m: int = 0,
                           interpret: bool = False) -> jax.Array:
    """One-pass Pallas triangular sweep (see module docstring)."""
    offsets = tuple(int(o) for o in offsets)
    _check_tri(bands, v, offsets, unit_diag, lower)
    if not lower:
        # Same back-to-front reduction as the reference: one kernel serves
        # both sweep directions.
        z = banded_trisweep_kernel(
            bands[:, ::-1] if bands.size else bands, v[::-1],
            tuple(-o for o in offsets), unit_diag=unit_diag, lower=True,
            block_m=block_m, interpret=interpret)
        return z[::-1]

    n = v.shape[0]
    out_dtype = jnp.promote_types(bands.dtype, v.dtype)
    acc = jnp.promote_types(out_dtype, jnp.float32)
    k_ring = max((-o for o in offsets), default=0) or 1
    bm = block_m or tuning.choose_trisweep_block(n, k_ring)
    bm = max(_round_up(bm, 8), _round_up(k_ring, 8))   # carry needs bm>=K
    n_pad = _round_up(n, bm)
    nb = max(len(offsets), 1)

    # Padded tail rows solve to v = 0: identity diagonal, zero off-diags.
    bands_p = jnp.pad(bands.astype(acc), ((0, 0), (0, n_pad - n)))
    if not unit_diag:
        pad_diag = jnp.arange(n_pad) >= n
        d0 = offsets.index(0)
        bands_p = bands_p.at[d0].set(jnp.where(pad_diag, 1.0, bands_p[d0]))
    if not bands.size:
        bands_p = jnp.zeros((1, n_pad), acc)
    v_p = jnp.pad(v.astype(acc), (0, n_pad - n))[:, None]

    z = pl.pallas_call(
        functools.partial(_trisweep_kernel, offsets=offsets,
                          unit_diag=unit_diag, k_ring=k_ring, bm=bm),
        grid=(n_pad // bm,),
        in_specs=[
            pl.BlockSpec((bm, nb), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), acc),
        scratch_shapes=[pltpu.VMEM((k_ring + bm, 1), acc)],
        interpret=interpret,
        name="gmres_precond_trisweep",
    )(bands_p.T, v_p)
    return z[:n, 0].astype(out_dtype)


def banded_trisweep(bands: jax.Array, v: jax.Array, offsets: tuple, *,
                    unit_diag: bool, lower: bool) -> jax.Array:
    """Dispatching entry point: kernel when the mode and VMEM footprint
    allow it, ``banded_trisweep_ref`` otherwise (identical results — the
    sweep is the same sequential recurrence either way)."""
    offsets = tuple(int(o) for o in offsets)
    mode = tuning.kernel_mode()
    k_ring = max((-o if lower else o for o in offsets), default=0) or 1
    if (mode == "ref" or v.ndim != 1
            or not tuning.trisweep_fits(v.shape[0], max(bands.shape[0], 1),
                                        bands.dtype, k=k_ring)):
        return banded_trisweep_ref(bands, v, offsets,
                                   unit_diag=unit_diag, lower=lower)
    return banded_trisweep_kernel(bands, v, offsets, unit_diag=unit_diag,
                                  lower=lower, interpret=mode == "interpret")
