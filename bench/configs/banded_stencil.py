"""One backward-Euler step of 2-D convection-diffusion: ``(I + tau S) x = b``.

``S`` is the five-point central-difference stencil of ``-Laplace(u) +
beta . grad(u)`` on an ``nx`` by ``ny`` grid in grid units, Dirichlet
boundaries, points ordered x fastest: the formula of the program's
``stencils.convection_diffusion_2d``, written out here so that the
yardstick does not move with the program.  ``tau`` is dt / h^2.  A time
stepper solves ``(I + tau S) u1 = u0`` from the first guess ``u0``, the
state of the step before; the first state is a fixed smooth field with a
seeded sign.  The
plain reference is the float64 band product on the host; the control
stores the bands and vectors in bfloat16, the precision below float32.
"""
from __future__ import annotations

import numpy as np


def offsets(cfg: dict):
    nx = int(cfg["nx"])
    return (-nx, -1, 0, 1, nx)


def _bands(cfg: dict):
    import jax.numpy as jnp

    nx, ny = int(cfg["nx"]), int(cfg["ny"])
    tau = float(cfg["tau"])
    bx, by = (float(b) / 2 for b in cfg["beta"])
    n = nx * ny
    i = jnp.arange(n)
    one = jnp.ones((n,), jnp.float32)
    zero = jnp.zeros((n,), jnp.float32)
    west = jnp.where(i % nx != 0, (-1 - bx) * one, zero)
    east = jnp.where(i % nx != nx - 1, (-1 + bx) * one, zero)
    south = jnp.where(i >= nx, (-1 - by) * one, zero)
    north = jnp.where(i < n - nx, (-1 + by) * one, zero)
    stencil = jnp.stack([south, west, 4 * one, east, north])
    return tau * stencil + jnp.zeros_like(stencil).at[2].set(one)


def smooth_fields(cfg: dict, key, count: int):
    """``count`` smooth fields on the grid, zero on the boundary: the sum
    of the sine modes ``p, q = 1 .. K`` of the grid with amplitude
    ``1 / (p^2 + q^2)`` and the signs of the configuration's fixed
    ``pattern``, times a sign drawn from ``key`` for each field.  A
    solve's Arnoldi steps do not depend on that sign, so every seed steps
    through the same work."""
    import jax
    import jax.numpy as jnp

    nx, ny = int(cfg["nx"]), int(cfg["ny"])
    k = int(cfg["initial"]["modes"])
    p = jnp.arange(1, k + 1, dtype=jnp.float32)
    sx = jnp.sin(jnp.pi * p[:, None] * jnp.arange(1, nx + 1) / (nx + 1))
    sy = jnp.sin(jnp.pi * p[:, None] * jnp.arange(1, ny + 1) / (ny + 1))
    amp = 1.0 / (p[:, None] ** 2 + p[None, :] ** 2)
    pattern = jax.random.rademacher(
        jax.random.PRNGKey(int(cfg["initial"]["pattern"])), (k, k),
        jnp.float32)
    sign = jax.random.rademacher(key, (count, 1, 1), jnp.float32)
    # field[y, x] = sum_{q, p} c[q, p] sy[q, y] sx[p, x], x fastest.
    fields = jnp.einsum("qy,cqp,px->cyx", sy, sign * pattern * amp, sx,
                        precision="highest")
    return fields.reshape(count, nx * ny)


def build(cfg: dict, key, pool: int):
    """The bands and ``pool`` smooth first states, on the device, in one
    jitted call."""
    import jax

    def make(key):
        return {"bands": _bands(cfg)}, smooth_fields(cfg, key, pool)

    return jax.jit(make)(key)


def program_operator(cfg: dict, arrays: dict):
    """The system as the program takes it."""
    from repro.core.operators import BandedOperator

    return BandedOperator(arrays["bands"], offsets(cfg), "pallas")


def shard_specs(cfg: dict, axis: str) -> dict:
    """How a cell on several chips places ``arrays``: the bands in column
    blocks, which are the matrix's row blocks, as the program's
    ``distributed.shard_specs`` places a ``BandedOperator``."""
    from jax.sharding import PartitionSpec as P

    return {"bands": P(None, axis)}


def shapes(cfg: dict, arrays: dict) -> dict:
    return {"n": int(cfg["nx"]) * int(cfg["ny"]),
            "nbands": len(offsets(cfg))}


def band_apply_f64(bands: np.ndarray, offs, x: np.ndarray) -> np.ndarray:
    """``y = A x`` for a stack of diagonals, ``a[i, i + off] = bands[d, i]``."""
    n = x.shape[-1]
    y = np.zeros_like(x)
    for d, off in enumerate(offs):
        lo, hi = max(0, -off), min(n, n - off)
        y[..., lo:hi] += bands[d, lo:hi] * x[..., lo + off:hi + off]
    return y


class HostSystem:
    """The plain reference: the bands in float64 on the host."""

    def __init__(self, cfg: dict, arrays: dict):
        self.bands = np.asarray(arrays["bands"], np.float64)
        self.offsets = offsets(cfg)

    def residuals(self, xs, bs) -> np.ndarray:
        """Relative residuals ``|b - A x| / |b|`` of the answers ``xs``
        and right-hand sides ``bs``, iterables read once, one answer at a
        time."""
        out = []
        for x, b in zip(xs, bs):
            x = np.asarray(x, np.float64)
            b = np.asarray(b, np.float64)
            r = b - band_apply_f64(self.bands, self.offsets, x)
            out.append(np.linalg.norm(r) / np.linalg.norm(b))
        return np.asarray(out)


def control_solve(cfg: dict, arrays: dict, b, tol, x0=None):
    """The plain reference solve in the program's place, with the bands
    and the vectors in bfloat16."""
    import jax.numpy as jnp

    from bench import reference

    bands = arrays["bands"].astype(jnp.bfloat16)
    offs = offsets(cfg)

    def matvec(v):
        v = v.astype(jnp.bfloat16)
        n = v.shape[0]
        y = jnp.zeros_like(v)
        for d, off in enumerate(offs):
            idx = jnp.arange(n) + off
            shifted = jnp.where((idx >= 0) & (idx < n), jnp.roll(v, -off), 0)
            y = y + bands[d] * shifted
        return y

    s = cfg["solver"]
    return reference.gmres(matvec, b, x0, m=s["m"], tol=tol,
                           max_restarts=s["max_restarts"],
                           dtype=jnp.bfloat16)
