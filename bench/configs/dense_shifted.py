"""Dense random nonsymmetric systems with a shifted spectrum.

The matrix is ``shift * I + scale * G / sqrt(n)``, ``G`` with ``N(0, 1)``
entries: Trefethen and Bau's GMRES example (Numerical Linear Algebra,
Lecture 35, Example 35.1: ``2 I + 0.5 G / sqrt(m)``).  Its eigenvalues
fill the disk of radius ``scale`` about ``shift``, so GMRES gains a factor
of about ``scale / shift`` a step.  The plain reference is the float64
product on the host; the control computes the product in the precision
below float32 at ``highest``: three bfloat16 passes.
"""
from __future__ import annotations

import numpy as np


def build(cfg: dict, key, pool: int):
    """The matrix and a pool of ``N(0, 1)`` right-hand sides, on the
    device, in one jitted call."""
    import jax
    import jax.numpy as jnp

    n = int(cfg["n"])
    shift, scale = float(cfg["shift"]), float(cfg["scale"])

    def make(key):
        ka, kb = jax.random.split(key)
        a = jax.random.normal(ka, (n, n), jnp.float32) * jnp.float32(
            scale / np.sqrt(n))
        a = a + shift * jnp.eye(n, dtype=jnp.float32)
        return {"a": a}, jax.random.normal(kb, (pool, n), jnp.float32)

    return jax.jit(make)(key)


def program_operator(cfg: dict, arrays: dict):
    """The system as the program takes it."""
    from repro.core.operators import DenseOperator

    return DenseOperator(arrays["a"], "pallas")


def shapes(cfg: dict, arrays: dict) -> dict:
    return {"n": int(cfg["n"])}


class HostSystem:
    """The plain reference: the matrix in float64 on the host."""

    def __init__(self, cfg: dict, arrays: dict):
        self.a = np.asarray(arrays["a"], np.float64)

    def residuals(self, xs, bs) -> np.ndarray:
        """Relative residuals ``|b - A x| / |b|`` of the answers ``xs``
        and right-hand sides ``bs``, iterables read once: stacked, in one
        product (a sample of N-vectors is small)."""
        xs = np.asarray(list(xs), np.float64)
        bs = np.asarray(list(bs), np.float64)
        r = bs - xs @ self.a.T
        return np.linalg.norm(r, axis=1) / np.linalg.norm(bs, axis=1)


def control_solve(cfg: dict, arrays: dict, b, tol):
    """The plain reference solve in the program's place, its products at
    ``high``: three bfloat16 passes with float32 accumulation."""
    from bench import reference

    s = cfg["solver"]
    a = arrays["a"]
    parts = reference.split3(a)
    return reference.gmres(lambda v: reference.dot3(a, v, parts), b, m=s["m"],
                           tol=tol, max_restarts=s["max_restarts"],
                           project=reference.dot3)
