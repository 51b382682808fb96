"""Pallas TPU kernels: one-pass BLOCK Gram-Schmidt (CGS2 + CholQR support).

Two block orthogonalization workloads share the same structural problem:
the basis V is the big operand, and the jnp reference streams it from HBM
once per level-2 product —

s-step GMRES (core/sstep.py) orthogonalizes an (s, n) power block W
against the (m1, n) basis with block-CGS2 + CholQR.  Per CGS2 pass the
reference makes TWO V streams (projection ``C = V W^T``, update
``W' = W - C^T V``) and the CholQR that follows re-streams W' for the
Gram matrix and again for the triangular solve: 4 passes over V plus
three W round-trips per block step.

``gmres_batched`` orthogonalizes k lanes, each against its OWN (m1, n)
basis; the vmapped jnp CGS2 streams every lane's basis four times per
Arnoldi step (2 passes x projection + update).

Both kernels here hold the basis block ENTIRELY in VMEM for the duration
of one grid step — the same residency bet ``arnoldi_fused`` makes, gated
by ``tuning.block_gs_fits`` — so V is read from HBM exactly once per pass
and the intermediates never leave the chip:

``block_gs_pass`` — one fused s-step pass.  Inputs V, W, a small (s, s)
  transform T and the row mask; ONE grid step computes

      Q   = T @ W                (CholQR back-substitution of the PREVIOUS
                                  pass, fused into this one's stream)
      C   = mask * (V Q^T)       block projection
      W'  = Q - C^T V            block update
      G   = W' W'^T              Gram matrix for the NEXT CholQR

  in-register.  The (s, s) Cholesky between passes is replicated
  collective-boundary algebra and stays OUTSIDE with the caller (exactly
  like the norm in ``arnoldi.finalize``): pass 1 runs with T = I, the
  caller Cholesky-factors G, and pass 2 receives T = inv(R1^T).  Per
  block step V is streamed twice (once per pass) instead of four times,
  and the 3 W round-trips disappear — the ``block_gs_*`` rows in
  benchmarks/kernel_bench.py model the ratio at ~0.48.

``batched_cgs2`` — the (k, m1, n) Gram-Schmidt for ``gmres_batched``.
  Grid (k,): each step holds ONE lane's basis in VMEM and runs BOTH CGS2
  passes against it (no CholQR — each lane orthogonalizes a single
  vector; normalization stays outside, at the psum boundary).  Each
  lane's V is streamed once per Arnoldi step instead of four times.

Both accumulate in f32 (f64 under x64) and upcast a bf16-stored basis
in-register, matching the other kernels in this package.

``block_gs_pass_ref`` is the psum-safe jnp fallback: with ``axis_name``
set, the C and G reductions complete across the row-sharded mesh — the
collective boundaries sit exactly where the kernel's outputs do, which is
why the sharded solve can fall back with identical semantics.

ROW-SHARDED kernel path (PR 5): the fused ``block_gs_pass`` cannot run
per-shard because the projection C must psum across shards BEFORE the
update consumes it.  ``block_gs_project`` / ``block_gs_update`` are the
same arithmetic split at exactly that boundary (the split-phase shape
``kernels/cgs2.py`` uses for the standard cycle):

    project kernel:  Q = T W;  C_partial = mask * (V_local Q^T)
    psum(C)          OUTSIDE, at the shard_map level
    update kernel:   W' = Q - C^T V_local;  G_partial = W' W'^T
    psum(G)          OUTSIDE — feeds the replicated CholQR

``block_gs_pass_sharded`` strings them together; per shard V streams once
per phase (twice per pass — the jnp reference's count) but the CholQR
Gram accumulates in-register with the update and W never round-trips
within a phase, and above all the sharded s-step cycle stays on the
kernel path instead of bailing to the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels import tuning


def _dot(a, b, dims, acc):
    return lax.dot_general(a, b, dimension_numbers=(dims, ((), ())),
                           preferred_element_type=acc)


# --------------------------------------------------------------------------
# s-step block pass: Q = T W;  C = mask * (V Q^T);  W' = Q - C^T V;  G = W'W'^T
# --------------------------------------------------------------------------
def _block_gs_kernel(v_ref, w_ref, t_ref, mask_ref, c_ref, wout_ref, g_ref):
    acc = g_ref.dtype
    v = v_ref[...].astype(acc)                        # (m1p, np) upcast
    q = _dot(t_ref[...], w_ref[...], ((1,), (0,)), acc)      # (sp, np)
    c = mask_ref[...] * _dot(v, q, ((1,), (1,)), acc)        # (m1p, sp)
    w2 = q - _dot(c, v, ((0,), (0,)), acc)                   # (sp, np)
    g = _dot(w2, w2, ((1,), (1,)), acc)                      # (sp, sp)
    c_ref[...] = c
    wout_ref[...] = w2
    g_ref[...] = g


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_gs_pass(v: jax.Array, w: jax.Array, tin: jax.Array,
                  mask: jax.Array, *, interpret: bool = False):
    """One fused block-GS pass.  v: (m1, n); w: (s, n); tin: (s, s);
    mask: (m1,).  Returns ``(c, w', g)`` — see the module docstring."""
    m1, n = v.shape
    s = w.shape[0]
    if w.shape[1] != n:
        raise TypeError(f"block_gs_pass: v {v.shape} and w {w.shape} must "
                        f"share the vector length")
    if tin.shape != (s, s) or mask.shape != (m1,):
        raise TypeError(f"block_gs_pass: tin {tin.shape} must be ({s}, {s}) "
                        f"and mask {mask.shape} ({m1},)")
    acc = jnp.promote_types(w.dtype, jnp.float32)
    m1p, np_, sp = tuning.choose_block_gs(m1, n, s, jnp.dtype(v.dtype).name)
    v = jnp.pad(v, ((0, m1p - m1), (0, np_ - n)))
    # Padded W rows / T rows are zero, so Q's padded rows — and with them
    # C's padded columns and G's padded block — stay exactly zero.
    w = jnp.pad(w.astype(acc), ((0, sp - s), (0, np_ - n)))
    tin = jnp.pad(tin.astype(acc), ((0, sp - s), (0, sp - s)))
    mask = jnp.pad(mask.astype(acc), (0, m1p - m1))

    c, w2, g = pl.pallas_call(
        _block_gs_kernel,
        grid=(1,),
        in_specs=[
            # Everything is ONE block: V fetched once, VMEM-resident for
            # projection AND update within this pass.
            pl.BlockSpec((m1p, np_), lambda _: (0, 0)),
            pl.BlockSpec((sp, np_), lambda _: (0, 0)),
            pl.BlockSpec((sp, sp), lambda _: (0, 0)),
            pl.BlockSpec((m1p, 1), lambda _: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((m1p, sp), lambda _: (0, 0)),
            pl.BlockSpec((sp, np_), lambda _: (0, 0)),
            pl.BlockSpec((sp, sp), lambda _: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m1p, sp), acc),
            jax.ShapeDtypeStruct((sp, np_), acc),
            jax.ShapeDtypeStruct((sp, sp), acc),
        ],
        interpret=interpret,
        name="gmres_block_gs",
    )(v, w, tin, mask[:, None])
    return c[:m1, :s], w2[:s, :n], g[:s, :s]


def block_gs_pass_ref(v: jax.Array, w: jax.Array, tin: jax.Array,
                      mask: jax.Array, axis_name=None):
    """jnp oracle / psum-safe fallback for ``block_gs_pass``.

    The two reductions (C and G) complete over ``axis_name`` when set —
    the collective rounds of the s-step method, one per reduction.
    """
    acc = jnp.promote_types(w.dtype, jnp.float32)
    q = tin.astype(acc) @ w.astype(acc)
    c = v.astype(acc) @ q.T
    if axis_name is not None:
        c = lax.psum(c, axis_name)
    c = c * mask.astype(acc)[:, None]
    w2 = q - c.T @ v.astype(acc)
    g = w2 @ w2.T
    if axis_name is not None:
        g = lax.psum(g, axis_name)
    return c, w2, g


# --------------------------------------------------------------------------
# Split-phase s-step pass for the row-sharded solve
# --------------------------------------------------------------------------
def _block_gs_project_kernel(v_ref, w_ref, t_ref, mask_ref, q_ref, c_ref):
    acc = c_ref.dtype
    v = v_ref[...].astype(acc)                               # (m1p, np)
    q = _dot(t_ref[...], w_ref[...], ((1,), (0,)), acc)      # (sp, np)
    c_ref[...] = mask_ref[...] * _dot(v, q, ((1,), (1,)), acc)
    q_ref[...] = q


def _block_gs_update_kernel(v_ref, q_ref, c_ref, wout_ref, g_ref):
    acc = g_ref.dtype
    v = v_ref[...].astype(acc)
    w2 = q_ref[...] - _dot(c_ref[...], v, ((0,), (0,)), acc)  # (sp, np)
    g_ref[...] = _dot(w2, w2, ((1,), (1,)), acc)              # (sp, sp)
    wout_ref[...] = w2


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_gs_project(v: jax.Array, w: jax.Array, tin: jax.Array,
                     mask: jax.Array, *, interpret: bool = False):
    """Projection phase: Q = T W and the PRE-psum C_partial = mask*(V Q^T).

    All arrays are local shards along the vector dim: v (m1, n_local), w
    (s, n_local), tin (s, s), mask (m1,).  Returns ``(q, c_partial)`` with
    q (s, n_local) — the transformed block the update phase consumes — and
    c_partial (m1, s), to be psum-completed by the caller.
    """
    m1, n = v.shape
    s = w.shape[0]
    if w.shape[1] != n:
        raise TypeError(f"block_gs_project: v {v.shape} and w {w.shape} "
                        f"must share the vector length")
    if tin.shape != (s, s) or mask.shape != (m1,):
        raise TypeError(f"block_gs_project: tin {tin.shape} must be "
                        f"({s}, {s}) and mask {mask.shape} ({m1},)")
    acc = jnp.promote_types(w.dtype, jnp.float32)
    m1p, np_, sp = tuning.choose_block_gs(m1, n, s, jnp.dtype(v.dtype).name)
    v = jnp.pad(v, ((0, m1p - m1), (0, np_ - n)))
    w = jnp.pad(w.astype(acc), ((0, sp - s), (0, np_ - n)))
    tin = jnp.pad(tin.astype(acc), ((0, sp - s), (0, sp - s)))
    mask = jnp.pad(mask.astype(acc), (0, m1p - m1))

    q, c = pl.pallas_call(
        _block_gs_project_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((m1p, np_), lambda _: (0, 0)),
            pl.BlockSpec((sp, np_), lambda _: (0, 0)),
            pl.BlockSpec((sp, sp), lambda _: (0, 0)),
            pl.BlockSpec((m1p, 1), lambda _: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((sp, np_), lambda _: (0, 0)),
            pl.BlockSpec((m1p, sp), lambda _: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sp, np_), acc),
            jax.ShapeDtypeStruct((m1p, sp), acc),
        ],
        interpret=interpret,
        name="gmres_block_gs_project",
    )(v, w, tin, mask[:, None])
    return q[:s, :n], c[:m1, :s]


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_gs_update(v: jax.Array, q: jax.Array, c: jax.Array, *,
                    interpret: bool = False):
    """Update phase: W' = Q - C^T V and the PRE-psum Gram G_partial = W'W'^T.

    ``c`` is the psum-COMPLETED (global) projection; v/q are local shards.
    Returns ``(w2, g_partial)`` — w2 (s, n_local), g_partial (s, s) to be
    psum-completed for the replicated CholQR outside.
    """
    m1, n = v.shape
    s = q.shape[0]
    if q.shape[1] != n or c.shape != (m1, s):
        raise TypeError(f"block_gs_update: v {v.shape} needs q ({s}, {n}) "
                        f"and c ({m1}, {s}); got {q.shape}, {c.shape}")
    acc = jnp.promote_types(q.dtype, jnp.float32)
    m1p, np_, sp = tuning.choose_block_gs(m1, n, s, jnp.dtype(v.dtype).name)
    v = jnp.pad(v, ((0, m1p - m1), (0, np_ - n)))
    q = jnp.pad(q.astype(acc), ((0, sp - s), (0, np_ - n)))
    c = jnp.pad(c.astype(acc), ((0, m1p - m1), (0, sp - s)))

    w2, g = pl.pallas_call(
        _block_gs_update_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((m1p, np_), lambda _: (0, 0)),
            pl.BlockSpec((sp, np_), lambda _: (0, 0)),
            pl.BlockSpec((m1p, sp), lambda _: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((sp, np_), lambda _: (0, 0)),
            pl.BlockSpec((sp, sp), lambda _: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sp, np_), acc),
            jax.ShapeDtypeStruct((sp, sp), acc),
        ],
        interpret=interpret,
        name="gmres_block_gs_update",
    )(v, q, c)
    return w2[:s, :n], g[:s, :s]


def block_gs_pass_sharded(v: jax.Array, w: jax.Array, tin: jax.Array,
                          mask: jax.Array, axis_name: str, *,
                          interpret: bool = False):
    """One row-sharded block-GS pass: split-phase kernels, psums between.

    Same (c, w', g) contract as ``block_gs_pass`` / ``block_gs_pass_ref``
    with all vector-dim arrays local shards; c and g return GLOBAL
    (psum-completed), matching where ``block_gs_pass_ref`` places its
    collectives — the s-step cycle cannot tell the implementations apart.
    """
    q, c = block_gs_project(v, w, tin, mask, interpret=interpret)
    c = lax.psum(c, axis_name)
    w2, g = block_gs_update(v, q, c, interpret=interpret)
    g = lax.psum(g, axis_name)
    return c, w2, g


# --------------------------------------------------------------------------
# Single-reduce s-step pass (gs="cgs2_pipelined"): ONE psum per pass
# --------------------------------------------------------------------------
def _block_gs_project_gram_kernel(v_ref, w_ref, t_ref, q_ref, c_ref, m_ref):
    acc = c_ref.dtype
    v = v_ref[...].astype(acc)                               # (m1p, np)
    q = _dot(t_ref[...], w_ref[...], ((1,), (0,)), acc)      # (sp, np)
    c_ref[...] = _dot(v, q, ((1,), (1,)), acc)   # UNMASKED C_hat = V Q^T
    m_ref[...] = _dot(q, q, ((1,), (1,)), acc)   # M = Q Q^T
    q_ref[...] = q


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_gs_project_gram(v: jax.Array, w: jax.Array, tin: jax.Array, *,
                          interpret: bool = False):
    """Single-reduce projection phase: Q = T W plus the PRE-psum payload
    halves ``C_hat_partial = V Q^T`` (UNMASKED — the Gram recurrence needs
    the full column) and ``M_partial = Q Q^T``, all from ONE stream of V/W.

    Returns ``(q, c_hat_partial, m_partial)``; the caller stacks the last
    two into one psum payload (``block_gs_pass_single_reduce``).
    """
    m1, n = v.shape
    s = w.shape[0]
    if w.shape[1] != n:
        raise TypeError(f"block_gs_project_gram: v {v.shape} and w "
                        f"{w.shape} must share the vector length")
    if tin.shape != (s, s):
        raise TypeError(f"block_gs_project_gram: tin {tin.shape} must be "
                        f"({s}, {s})")
    acc = jnp.promote_types(w.dtype, jnp.float32)
    m1p, np_, sp = tuning.choose_block_gs(m1, n, s, jnp.dtype(v.dtype).name)
    v = jnp.pad(v, ((0, m1p - m1), (0, np_ - n)))
    w = jnp.pad(w.astype(acc), ((0, sp - s), (0, np_ - n)))
    tin = jnp.pad(tin.astype(acc), ((0, sp - s), (0, sp - s)))

    q, c, mm = pl.pallas_call(
        _block_gs_project_gram_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((m1p, np_), lambda _: (0, 0)),
            pl.BlockSpec((sp, np_), lambda _: (0, 0)),
            pl.BlockSpec((sp, sp), lambda _: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((sp, np_), lambda _: (0, 0)),
            pl.BlockSpec((m1p, sp), lambda _: (0, 0)),
            pl.BlockSpec((sp, sp), lambda _: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sp, np_), acc),
            jax.ShapeDtypeStruct((m1p, sp), acc),
            jax.ShapeDtypeStruct((sp, sp), acc),
        ],
        interpret=interpret,
        name="gmres_block_gs_project_gram",
    )(v, w, tin)
    return q[:s, :n], c[:m1, :s], mm[:s, :s]


def _sr_recover_block(payload, mask, gram, m1):
    """Replicated recovery of (c, g, c_hat) from the stacked psum payload.

    With Gamma = ``gram`` the maintained basis Gram matrix (~= V V^T), the
    CholQR Gram of the updated block W' = Q - C^T V is exactly

        G = M - C_hat^T C - C^T C_hat + C^T Gamma C

    — no second reduction: the W'W'^T psum of the split-phase pass is
    replaced by collective-free (m x s) algebra.
    """
    c_hat = payload[:m1]
    mm = payload[m1:]
    c = c_hat * mask[:, None]
    gc = gram @ c
    g = mm - c_hat.T @ c - c.T @ c_hat + c.T @ gc
    return c, g, c_hat


def block_gs_pass_single_reduce(v: jax.Array, w: jax.Array, tin: jax.Array,
                                mask: jax.Array, gram: jax.Array,
                                axis_name=None, *, interpret: bool = False):
    """One single-reduce block-GS pass: ONE stacked psum instead of two.

    Same ``(c, w', g)`` contract as ``block_gs_pass_sharded`` plus the raw
    ``c_hat`` column (the caller maintains the basis Gram matrix ``gram``
    with it).  The projection kernel emits the unmasked C_hat = V Q^T and
    M = Q Q^T from one stream; both cross shards as ONE stacked
    (m1 + s, s) payload, and the CholQR Gram is recovered from it against
    ``gram`` (see ``_sr_recover_block``).  The update kernel's own Gram
    output is discarded — its psum is the round being saved.
    """
    m1 = v.shape[0]
    q, c_hat, mm = block_gs_project_gram(v, w, tin, interpret=interpret)
    payload = jnp.concatenate([c_hat, mm], axis=0)
    if axis_name is not None:
        payload = lax.psum(payload, axis_name)           # the ONE collective
    c, g, c_hat = _sr_recover_block(payload, mask.astype(payload.dtype),
                                    gram, m1)
    w2, _ = block_gs_update(v, q, c, interpret=interpret)
    return c, w2, g, c_hat


def block_gs_pass_single_reduce_ref(v: jax.Array, w: jax.Array,
                                    tin: jax.Array, mask: jax.Array,
                                    gram: jax.Array, axis_name=None):
    """jnp oracle / psum-safe fallback for ``block_gs_pass_single_reduce``
    — identical payload stacking and the same single psum placement."""
    acc = jnp.promote_types(w.dtype, jnp.float32)
    m1 = v.shape[0]
    va = v.astype(acc)
    q = tin.astype(acc) @ w.astype(acc)
    payload = jnp.concatenate([va @ q.T, q @ q.T], axis=0)
    if axis_name is not None:
        payload = lax.psum(payload, axis_name)
    c, g, c_hat = _sr_recover_block(payload, mask.astype(acc), gram, m1)
    w2 = q - c.T @ va
    return c, w2, g, c_hat


# --------------------------------------------------------------------------
# batched per-lane CGS2 for gmres_batched
# --------------------------------------------------------------------------
def _batched_cgs2_kernel(v_ref, w_ref, mask_ref, h_ref, wout_ref):
    acc = h_ref.dtype
    v = v_ref[0].astype(acc)                          # (m1p, np) this lane
    w = w_ref[0]                                      # (1, np)
    mask = mask_ref[0]                                # (1, m1p)
    # Both CGS2 passes against the VMEM-resident lane basis; h and the
    # intermediate w' never exist in HBM.
    h1 = mask * _dot(w, v, ((1,), (1,)), acc)         # (1, m1p) project
    w1 = w - _dot(h1, v, ((1,), (0,)), acc)           # (1, np)   update
    h2 = mask * _dot(w1, v, ((1,), (1,)), acc)        # reorthogonalize
    w2 = w1 - _dot(h2, v, ((1,), (0,)), acc)
    h_ref[0] = h1 + h2
    wout_ref[0] = w2


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_cgs2(v: jax.Array, w: jax.Array, mask: jax.Array, *,
                 interpret: bool = False):
    """Per-lane CGS2, one lane's basis VMEM-resident per grid step.

    v: (k, m1, n) per-lane bases; w: (k, n) fresh mat-vec outputs; mask:
    (k, m1) per-lane valid-row masks (lanes sit at different step counts).
    Returns ``(h, w'')`` with h (k, m1) and w'' (k, n) — unnormalized, the
    per-lane norm/breakdown probe stays outside (``arnoldi.finalize``).
    """
    k, m1, n = v.shape
    if w.shape != (k, n) or mask.shape != (k, m1):
        raise TypeError(f"batched_cgs2: v {v.shape} needs w ({k}, {n}) and "
                        f"mask ({k}, {m1}); got {w.shape}, {mask.shape}")
    acc = jnp.promote_types(w.dtype, jnp.float32)
    m1p, np_, _ = tuning.choose_block_gs(m1, n, 1, jnp.dtype(v.dtype).name)
    # Per-lane rows travel as (k, 1, len) so each block's last two dims
    # equal the array's — Mosaic tiles a (1, len) block of a (k, len)
    # array only when k rows would fill the sublane tile.
    v = jnp.pad(v, ((0, 0), (0, m1p - m1), (0, np_ - n)))
    w = jnp.pad(w.astype(acc), ((0, 0), (0, np_ - n)))[:, None, :]
    mask = jnp.pad(mask.astype(acc), ((0, 0), (0, m1p - m1)))[:, None, :]

    h, w2 = pl.pallas_call(
        _batched_cgs2_kernel,
        grid=(k,),
        in_specs=[
            pl.BlockSpec((1, m1p, np_), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, np_), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, m1p), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, m1p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, np_), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, 1, m1p), acc),
            jax.ShapeDtypeStruct((k, 1, np_), acc),
        ],
        interpret=interpret,
        name="gmres_block_gs_batched",
    )(v, w, mask)
    return h[:, 0, :m1], w2[:, 0, :n]
