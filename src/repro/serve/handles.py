"""Solver handles: pre-lowered batched cycles behind an LRU.

Admission must never pay a compile.  A :class:`SolverHandle` wraps ONE
jitted ``gmres_batched_cycle`` for a fixed ``(n, operator fmt, m, k,
dtype)`` bucket; jax compiles it on the handle's FIRST cycle and every
later request in the bucket reuses the executable.  The
:class:`HandleCache` is a bounded LRU (kernels/tuning.LruCache) over
those buckets — the compiled-executable complement of the on-disk
``persistent_choice`` cache, which already makes the tile choices INSIDE
the lowering restart-stable.

A handle serves ONE concrete operator (and gs scheme and
preconditioner), not just a shape — so the cache key must name it too.
:class:`HandleKey` therefore carries identity tokens for the operator
and preconditioner alongside the shape bucket: two servers sharing one
cache over same-shaped but different operators get two handles, never
each other's system.  The tokens are ``id()``s, which is sound here
because the cached handle holds strong references to both objects — a
token can only collide with a DEAD operator, and a dead operator cannot
be passed to ``get``.

The handle's kernel dispatch is the solver core's, untouched: CGS2-family
schemes go through the batched block-GS kernel when ``tuning.kernel_mode``
and ``tuning.block_gs_fits`` allow, and degrade to the vmapped jnp
reference otherwise — which is exactly the VMEM-overflow fallback the
fault-injection tests force.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.gmres import gmres_batched_cycle
from repro.kernels.tuning import LruCache


def operator_fmt(op) -> str:
    """Stable format tag for handle keys ('dense', 'sparse', 'banded'...)."""
    name = type(op).__name__
    if name.endswith("Operator"):
        return name[:-len("Operator")].lower()
    if hasattr(op, "ndim"):          # raw dense array
        return "dense"
    return "function"


def operator_dim(op) -> int:
    shape = getattr(op, "shape", None)
    if shape is None:
        raise ValueError(
            "operator has no .shape; wrap it in a FunctionOperator so the "
            "server can size its lanes")
    return int(shape[0])


class HandleKey(NamedTuple):
    """LRU key: everything that changes the lowered cycle.

    The shape bucket ``(n, fmt, m, k, dtype)`` sizes the executable; the
    identity fields pin WHICH system it solves — the handle holds the
    operator, gs scheme, and preconditioner, so a key that ignored them
    would hand a same-shaped server the wrong solve.
    """

    n: int
    fmt: str
    m: int
    k: int
    dtype: str
    gs: str
    op_token: int                # id(op): live while the handle is cached
    precond_token: int           # id(precond), 0 for None


def _handle_key(op, *, m: int, k: int, dtype, gs: str,
                precond) -> HandleKey:
    return HandleKey(n=operator_dim(op), fmt=operator_fmt(op),
                     m=int(m), k=int(k), dtype=jnp.dtype(dtype).name,
                     gs=str(gs), op_token=id(op),
                     precond_token=0 if precond is None else id(precond))


class SolverHandle:
    """One operator bucket's jitted lockstep cycle.

    ``jax.jit`` is lazy, so constructing a handle is cheap; the compile
    lands on the first ``cycle`` call and is keyed by the (k, n) block
    shapes, which the handle pins.  The handle holds its operator and
    passes it to every cycle — one handle per A, which is the batched
    engine's contract (ONE A stream shared by all k lanes).
    """

    def __init__(self, op, *, m: int = 30, k: int = 8,
                 dtype=jnp.float32, gs: str = "cgs2",
                 precond=None):
        self.op = op
        self.precond = precond   # strong ref: keeps the key token valid
        self.key = _handle_key(op, m=m, k=k, dtype=dtype, gs=gs,
                               precond=precond)
        self.gs = gs
        # The operator is a jit ARGUMENT, not a closure: a closed-over
        # array would be baked into the program as a literal (268 MB for
        # an 8192^2 f32 matrix), bloating compile time and memory.
        cycle = functools.partial(gmres_batched_cycle, m=int(m), gs=gs,
                                  precond=precond, compute_dtype=dtype)

        def batched_cycle(*args, **kwargs):
            # Runs only when jit traces the cycle: a count above 1 means a
            # call missed the compiled executable and recompiled.
            self.traces += 1
            return cycle(*args, **kwargs)

        self._cycle = jax.jit(batched_cycle)
        self.cycles_run = 0
        self.traces = 0

    @property
    def n(self) -> int:
        return self.key.n

    @property
    def k(self) -> int:
        return self.key.k

    @property
    def m(self) -> int:
        return self.key.m

    def block_shape(self) -> Tuple[int, int]:
        return (self.key.k, self.key.n)

    def lower(self):
        """The cycle lowered for this handle's block shapes (all lanes
        active), e.g. to ``.compile()`` and read the program."""
        dt = jnp.dtype(self.key.dtype)
        blk = jnp.zeros(self.block_shape(), dt)
        return self._cycle.lower(self.op, blk, blk,
                                 tol_abs=jnp.zeros((self.k,), dt),
                                 active=jnp.ones((self.k,), bool))

    def cycle(self, b, x, tol_abs, active):
        """One lockstep restart cycle; returns ``(x', beta', inner_steps)``.

        All arguments are full (k, n) / (k,) blocks — idle lanes ride
        along masked out (their x passes through untouched), which keeps
        one executable valid for every occupancy level.
        """
        dt = jnp.dtype(self.key.dtype)
        b = jnp.asarray(b, dt)
        x = jnp.asarray(x, dt)
        if b.shape != self.block_shape() or x.shape != self.block_shape():
            raise ValueError(
                f"handle {self.key} expects {self.block_shape()} blocks, "
                f"got b{b.shape} x{x.shape}")
        out = self._cycle(self.op, b, x, tol_abs=jnp.asarray(tol_abs, dt),
                          active=jnp.asarray(active, bool))
        self.cycles_run += 1
        return out


class HandleCache:
    """LRU of :class:`SolverHandle`, keyed by :class:`HandleKey`.

    ``get`` is the only entry point: hit moves the handle to the front,
    miss builds one (cheap — lowering is lazy) and may evict the coldest
    bucket, dropping its compiled executable with it.  Stats surface as
    ``solver_serve_*`` metrics so cache thrash is visible in the bench.

    Sharing one cache across servers (``SolverServer(handle_cache=...)``)
    is safe because the key carries operator/gs/precond identity, not
    just the shape bucket; a hit is additionally asserted to resolve to
    the SAME operator object before the handle is handed out.
    """

    def __init__(self, maxsize: int = 8):
        self._lru = LruCache(maxsize=maxsize)

    def __len__(self) -> int:
        return len(self._lru)

    def __contains__(self, key) -> bool:
        return key in self._lru

    def get(self, op, *, m: int = 30, k: int = 8, dtype=jnp.float32,
            gs: str = "cgs2", precond=None) -> SolverHandle:
        key = _handle_key(op, m=m, k=k, dtype=dtype, gs=gs,
                          precond=precond)
        handle = self._lru.get_or_create(
            key, lambda: SolverHandle(op, m=m, k=k, dtype=dtype, gs=gs,
                                      precond=precond))
        assert handle.op is op and handle.gs == gs, (
            f"handle cache integrity: key {key} resolved to a different "
            f"operator/scheme")
        return handle

    def stats(self) -> dict:
        return self._lru.stats()

    def clear(self) -> None:
        self._lru.clear()
