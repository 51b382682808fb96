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
  upper backward substitution).  The Pallas kernel is line-blocked and
  lane-dense: v, z and every band travel as (rows, 128) arrays (a free
  reshape when n is a multiple of 1024, else one pad), and the rows go in
  blocks of 1024, one f32 (8, 128) vreg, several blocks a grid step.  A
  band that reaches a whole number of blocks is one aligned multiply-add
  against a block solved earlier and kept in a VMEM scratch ring; the +-1
  chain inside a block is a first-order linear recurrence, solved by a
  10-level Hillis-Steele scan of affine maps built from lane and sublane
  rolls.  The upper sweep is the same kernel walking the blocks in
  reverse (a reversed ``index_map``, mirrored rolls); nothing is flipped.
  One HBM read of bands and v, one write of z.

  Dispatch is decided at trace time from what the call shows: the kernel
  runs where the mode allows kernels (``tuning.kernel_mode``), v is one
  vector, and every band other than the diagonal and +-1 sits a multiple
  of 1024 rows away (a 2-D stencil on a grid 1024 wide, or no far band at
  all, as ``line_jacobi``); ``tuning.trisweep_fits`` is the VMEM gate.
  Everything else takes ``banded_trisweep_ref``, the blocked jnp oracle
  (also the vmapped multi-RHS path), which reads the upper sweep as a
  lower one on flipped bands.  ``DISPATCH`` counts each choice.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning


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
# Triangular sweep: line-blocked Pallas kernel
# --------------------------------------------------------------------------
LANE = tuning.LANE
SUB = 8                     # f32 sublanes: a block is one (8, 128) vreg
BLOCK = SUB * LANE          # 1024 rows, flattened row-major
LEVELS = BLOCK.bit_length() - 1

# Trace-time record of ``banded_trisweep``'s choices: ``("kernel", why)``
# or ``("ref", why)`` -> number of sweeps traced that way.
DISPATCH = collections.Counter()


def _flat_shift(x, d, forward, lane):
    """Shift a block along its 1024 flattened rows: element i takes
    element i - d (``forward``) or i + d.  ``d`` is below 128 (a lane
    roll, the wrapped lanes taken from the neighbouring sublane) or a
    multiple of 128 (a sublane roll).  The d elements with no source hold
    wrapped values; callers mask them."""
    q, r = divmod(d, LANE)
    if not r:
        return pltpu.roll(x, q if forward else SUB - q, 0)
    if forward:
        y = pltpu.roll(x, r, 1)
        return jnp.where(lane >= r, y, pltpu.roll(y, 1, 0))
    y = pltpu.roll(x, LANE - r, 1)
    return jnp.where(lane < LANE - r, y, pltpu.roll(y, SUB - 1, 0))


def _sweep_kernel(b_ref, v_ref, o_ref, zs_ref, *, offsets, unit_diag,
                  forward, blocks, ring):
    """``blocks`` blocks of 1024 rows per grid step, solved in sweep order.

    zs_ref holds solved blocks in sweep order: the first ``ring`` are the
    last blocks of the step before, then this step's.  A far band of
    reach m blocks reads the block solved m before, whole and aligned; the
    +-1 chain ``z_i = a_i z_(i-1) + w_i`` inside a block is a Hillis-Steele
    scan of the affine maps ``(a_i, w_i)``, its first map carrying the
    boundary row of the block solved just before.
    """
    g = pl.program_id(0)
    idx = {off: d for d, off in enumerate(offsets)}
    near = idx.get(-1 if forward else 1)
    far = [(d, abs(off) // BLOCK) for d, off in enumerate(offsets)
           if abs(off) > 1]
    sub = lax.broadcasted_iota(jnp.int32, (SUB, LANE), 0)
    lane = lax.broadcasted_iota(jnp.int32, (SUB, LANE), 1)
    pos = sub * LANE + lane if forward else BLOCK - 1 - (sub * LANE + lane)

    @pl.when(g == 0)
    def _seed():
        zs_ref[...] = jnp.zeros_like(zs_ref)

    def solved(u):
        return pl.ds(pl.multiple_of((ring + u) * SUB, SUB), SUB)

    def block(u, carry):
        t = u if forward else blocks - 1 - u
        rows = pl.ds(pl.multiple_of(t * SUB, SUB), SUB)
        w = v_ref[rows, :]
        for d, m in far:
            w = w - b_ref[d, rows, :] * zs_ref[solved(u - m), :]
        a = None if near is None else -b_ref[near, rows, :]
        if not unit_diag:
            diag = b_ref[idx[0], rows, :]
            w = w / diag
            a = None if a is None else a / diag
        if a is not None:
            # pos counts rows in sweep order, so pos == 0 is the block's
            # first row to solve: fold the boundary row in there.
            z_in = _flat_shift(zs_ref[solved(u - 1), :], 1, forward, lane)
            w = jnp.where(pos == 0, w + a * z_in, w)
            p = jnp.where(pos == 0, 0.0, a)
            for lvl in range(LEVELS):
                s = 1 << lvl
                keep = pos < s
                qs = _flat_shift(w, s, forward, lane)
                if lvl + 1 < LEVELS:
                    ps = _flat_shift(p, s, forward, lane)
                w = jnp.where(keep, w, p * qs + w)
                if lvl + 1 < LEVELS:
                    p = jnp.where(keep, p, p * ps)
        zs_ref[solved(u), :] = w
        o_ref[rows, :] = w
        return carry

    lax.fori_loop(0, blocks, block, 0)
    zs_ref[:ring * SUB, :] = zs_ref[blocks * SUB:(blocks + ring) * SUB, :]


def _unfit(bands, offsets, dtype, ndim: int = 1) -> str | None:
    """Why the kernel cannot take these bands, or None: it needs one
    vector (``ndim`` 1), at least one band, f32 arithmetic, and every band
    other than +-1 and the diagonal a whole number of 1024-row blocks
    away."""
    if ndim != 1:
        return "batched"
    if not bands.size:
        return "no_bands"
    if jnp.promote_types(dtype, jnp.float32) != jnp.float32:
        return "dtype"
    if any(abs(o) > 1 and abs(o) % BLOCK for o in offsets):
        return "far_band_unaligned"
    return None


def _ring(offsets) -> int:
    """Solved blocks carried between grid steps: the deepest far reach,
    and at least the one block the +-1 chain continues from."""
    return max(max((abs(o) // BLOCK for o in offsets), default=0), 1)


def _lane_dense(bands, offsets, unit_diag):
    nbands, n = bands.shape
    n_pad = -(-n // BLOCK) * BLOCK
    tiles = bands.astype(jnp.float32)
    if n_pad != n:
        # Padded rows solve to 0: zero bands, v = 0, a unit diagonal.
        tiles = jnp.pad(tiles, ((0, 0), (0, n_pad - n)))
        if not unit_diag:
            tiles = tiles.at[offsets.index(0), n:].set(1.0)
    return tiles.reshape(nbands, n_pad // LANE, LANE)


def kernel_tiles(bands: jax.Array, offsets: tuple, *,
                 unit_diag: bool) -> jax.Array | None:
    """The kernel's band layout: f32 ``(nbands, rows, 128)``, padded to
    whole 1024-row blocks; None where the kernel cannot take these bands.

    A preconditioner builds it once, at factor time, and passes it to
    ``banded_trisweep`` as ``tiles=``: a (nbands, n) array does not share
    the tiled HBM layout of its lane-dense reshape, so deriving the tiles
    inside every sweep would copy the bands each time."""
    offsets = tuple(int(o) for o in offsets)
    if _unfit(bands, offsets, bands.dtype):
        return None
    return _lane_dense(bands, offsets, unit_diag)


@functools.partial(
    jax.jit, static_argnames=("offsets", "unit_diag", "lower", "interpret"))
def banded_trisweep_kernel(bands: jax.Array, v: jax.Array, offsets: tuple, *,
                           unit_diag: bool, lower: bool,
                           interpret: bool = False) -> jax.Array:
    """Line-blocked Pallas triangular sweep (see module docstring).

    ``bands`` is the (nbands, n) band stack or its ``kernel_tiles``."""
    offsets = tuple(int(o) for o in offsets)
    n = v.shape[0]
    nbands = len(offsets)
    nblk = -(-n // BLOCK)
    out_dtype = jnp.promote_types(bands.dtype, v.dtype)
    why = _unfit(bands, offsets, out_dtype, v.ndim)
    if why is not None:
        raise ValueError(f"banded_trisweep_kernel: cannot take offsets "
                         f"{offsets} on v {v.shape} ({why})")
    if bands.ndim == 2:
        _check_tri(bands, v, offsets, unit_diag, lower)
        bands = _lane_dense(bands, offsets, unit_diag)
    elif bands.shape != (nbands, nblk * SUB, LANE):
        raise TypeError(f"banded_trisweep_kernel: tiles {bands.shape} for "
                        f"{nbands} bands of {n} rows")
    f32 = jnp.float32
    ring = _ring(offsets)
    blocks = tuning.choose_trisweep_blocks(nblk, nbands, ring)
    steps = nblk // blocks
    rows = nblk * SUB
    v_p = jnp.pad(v.astype(f32), (0, nblk * BLOCK - n))
    if lower:
        tile = lambda g: (g, 0)
    else:
        tile = lambda g: (steps - 1 - g, 0)

    z = pl.pallas_call(
        functools.partial(_sweep_kernel, offsets=offsets,
                          unit_diag=unit_diag, forward=lower, blocks=blocks,
                          ring=ring),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((nbands, blocks * SUB, LANE),
                         lambda g: (0,) + tile(g)),
            pl.BlockSpec((blocks * SUB, LANE), tile),
        ],
        out_specs=pl.BlockSpec((blocks * SUB, LANE), tile),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), f32),
        scratch_shapes=[pltpu.VMEM(((ring + blocks) * SUB, LANE), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gmres_precond_trisweep",
    )(bands, v_p.reshape(rows, LANE))
    return z.reshape(rows * LANE)[:n].astype(out_dtype)


def banded_trisweep(bands: jax.Array, v: jax.Array, offsets: tuple, *,
                    unit_diag: bool, lower: bool,
                    tiles: jax.Array | None = None) -> jax.Array:
    """Dispatching entry point: the line-blocked kernel where the mode
    allows kernels, v is one vector and ``_unfit`` and the VMEM gate find
    nothing; ``banded_trisweep_ref`` otherwise (the same recurrence, in
    another association order).  ``tiles``: the bands' ``kernel_tiles``,
    if the caller keeps them.  Each choice is counted in ``DISPATCH``
    when it is traced."""
    offsets = tuple(int(o) for o in offsets)
    mode = tuning.kernel_mode()
    why = "mode" if mode == "ref" else _unfit(
        bands, offsets, jnp.promote_types(bands.dtype, v.dtype), v.ndim)
    if why is None and not tuning.trisweep_fits(len(offsets), _ring(offsets)):
        why = "vmem"
    if why is not None:
        DISPATCH["ref", why] += 1
        return banded_trisweep_ref(bands, v, offsets,
                                   unit_diag=unit_diag, lower=lower)
    _check_tri(bands, v, offsets, unit_diag, lower)
    far = any(abs(o) > 1 for o in offsets)
    DISPATCH["kernel", "far_band" if far else "no_far_band"] += 1
    return banded_trisweep_kernel(bands if tiles is None else tiles, v,
                                  offsets, unit_diag=unit_diag, lower=lower,
                                  interpret=mode == "interpret")
