"""A plain restarted GMRES(m), for the controls.

It imports nothing of the program.  Arnoldi with classical Gram-Schmidt
applied twice, the small least-squares problem solved with ``lstsq`` at the
end of each cycle, and a restart from the recomputed residual: no kernels,
no early exit inside a cycle, no preconditioner.  Put in the program's
place with its products in a lower precision, it is the control that the
check has to refuse.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _round_bf16(x):
    """``x`` (float32) rounded to nearest even at bfloat16's 8 bits of
    precision, kept in float32.

    Integer arithmetic on the bits, not a round trip through bfloat16:
    XLA may drop a float32 -> bfloat16 -> float32 round trip as excess
    precision.  The bfloat16 conversion of the result is exact."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                    jnp.float32)


def split3(x):
    """``x`` as a high and a low bfloat16 part, as ``high`` splits it."""
    x = x.astype(jnp.float32)
    hi = _round_bf16(x)
    lo = _round_bf16(x - hi)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def dot3(x, y, x_parts=None):
    """``x @ y`` at ``high``: three bfloat16 passes, float32 accumulation.
    ``x_parts`` is ``split3(x)`` where the caller has it already."""
    (x_hi, x_lo), (y_hi, y_lo) = x_parts or split3(x), split3(y)

    def dot(p, q):
        return jnp.dot(p, q, preferred_element_type=jnp.float32)

    return dot(x_hi, y_hi) + dot(x_hi, y_lo) + dot(x_lo, y_hi)


def gmres(matvec, b, x0=None, *, m: int, tol: float, max_restarts: int,
          dtype=jnp.float32, project=None):
    """Solve ``A x = b`` with ``matvec`` for ``A`` from the first guess
    ``x0`` (zeros by default), vectors stored in ``dtype``;
    ``project(V, w)`` gives ``V @ w`` (default: in ``dtype``).  Returns
    ``x`` in float32."""
    f32 = jnp.float32
    if project is None:
        def project(v, w):
            return v @ w
    b = b.astype(dtype)
    n = b.shape[0]
    bnorm = jnp.linalg.norm(b.astype(f32))
    tiny = f32(1e-30)

    def orth(v, w, mask):
        c = project(v, w).astype(f32) * mask
        return c, (w.astype(f32) - c @ v.astype(f32)).astype(dtype)

    def cycle(x):
        r = (b - matvec(x)).astype(dtype)
        beta = jnp.linalg.norm(r.astype(f32))
        v = jnp.zeros((m + 1, n), dtype).at[0].set(
            (r.astype(f32) / jnp.maximum(beta, tiny)).astype(dtype))
        h = jnp.zeros((m + 1, m), f32)

        def step(j, carry):
            v, h = carry
            w = matvec(v[j]).astype(dtype)
            mask = (jnp.arange(m + 1) <= j).astype(f32)
            c, w = orth(v, w, mask)
            c2, w = orth(v, w, mask)
            nw = jnp.linalg.norm(w.astype(f32))
            h = h.at[:, j].set((c + c2).at[j + 1].set(nw))
            v = v.at[j + 1].set((w.astype(f32) / jnp.maximum(nw, tiny))
                                .astype(dtype))
            return v, h

        v, h = lax.fori_loop(0, m, step, (v, h))
        e1 = jnp.zeros((m + 1,), f32).at[0].set(beta)
        y = jnp.linalg.lstsq(h, e1)[0]
        return (x.astype(f32) + y @ v[:m].astype(f32)).astype(dtype)

    def cond(c):
        x, k = c
        r = b.astype(f32) - matvec(x).astype(f32)
        return (jnp.linalg.norm(r) > tol * bnorm) & (k < max_restarts)

    def body(c):
        x, k = c
        return cycle(x), k + 1

    x0 = jnp.zeros((n,), dtype) if x0 is None else x0.astype(dtype)
    x, _ = lax.while_loop(cond, body, (x0, 0))
    return x.astype(f32)
