"""Preconditioning subsystem (core/preconditioners.py + kernels/trisolve.py).

  1. kernel correctness: ILU(0) factor vs dense oracle, trisweep kernel
     vs scan ref, fused Chebyshev kernel vs the plain recurrence, the
     shifted (Newton-basis) matrix-powers variant, the ELL powers kernel;
  2. the spectral-interval estimator UPPER-bounds the spectrum (an
     underestimated lam_max flips A.M^-1 indefinite — the one direction
     Chebyshev cannot tolerate);
  3. parity: preconditioned solves reach the same solution as
     unpreconditioned within tol with STRICTLY fewer restarts on the 2-D
     Poisson and convection-diffusion stencils, for gmres / gmres_sstep /
     gmres_batched and the pipelined gs;
  4. scale invariance at c in {1e-6, 1e6} (the PR 3 contract);
  5. every public solver honors precond= or raises a clear ValueError;
  6. dispatch spies: the fused Chebyshev / trisweep / ELL-powers kernels
     actually engage when they fit, and a forced VMEM-overflow verdict
     degrades to the identical-result reference;
  7. serve admission: a precond/operator mismatch is refused at
     construction with the FIELD NAMED, never inside a lane;
  8. hypothesis property: random SPD stencil x precond x fmt converges
     and matches the dense oracle.

The 4-fake-device sharded composition (halo-exchange Chebyshev, shard-
local banded block-Jacobi, one-psum-per-step pipelined HLO) runs in a
subprocess, same pattern as tests/test_distributed.py.
"""
import collections
import importlib.util
import inspect
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import preconditioners as P
from repro.core import operators, stencils
from repro.core.gmres import gmres, gmres_batched, gmres_batched_cycle
from repro.core.sstep import gmres_sstep
from repro.kernels import matrix_powers, trisolve, tuning

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _dense_of(op) -> np.ndarray:
    return np.asarray(op.todense())


def _rel_err(x, y):
    return float(np.linalg.norm(np.asarray(x) - np.asarray(y))
                 / max(np.linalg.norm(np.asarray(y)), 1e-30))


def _sym_banded(key, n, *, halo=1, dtype=jnp.float32):
    """Random symmetric diagonally-dominant banded operator (SPD)."""
    offs = tuple(range(-halo, halo + 1))
    vals = jax.random.uniform(key, (halo, n), minval=0.1, maxval=1.0)
    rows = []
    for off in offs:
        if off == 0:
            rows.append(jnp.zeros((n,)))
        elif off > 0:
            rows.append(-vals[off - 1])                   # A[i, i+off]
        else:
            rows.append(-jnp.roll(vals[-off - 1], -off))  # A[i-1,i] mirrored
    bands = jnp.stack(rows)
    bands = trisolve._mask_oob(bands, offs)
    diag = jnp.sum(jnp.abs(bands), axis=0) + 0.5
    bands = bands.at[offs.index(0)].set(diag)
    return operators.BandedOperator(bands.astype(dtype), offs)


# --------------------------------------------------------------------------
# 1. kernel correctness
# --------------------------------------------------------------------------
def test_ilu0_tridiagonal_is_exact():
    """On a tridiagonal pattern ILU(0) IS the LU factorization."""
    op = _sym_banded(jax.random.PRNGKey(0), 48, halo=1)
    pc = P.banded_ilu0(op)
    v = jax.random.normal(jax.random.PRNGKey(1), (48,))
    exact = np.linalg.solve(_dense_of(op), np.asarray(v))
    np.testing.assert_allclose(np.asarray(pc(v)), exact, rtol=2e-4,
                               atol=2e-4)


def test_ilu0_pentadiagonal_residual_small():
    """ILU(0) on the 2-D Poisson pattern: ||L U - A|| confined to fill-in."""
    op = stencils.poisson_2d(6)
    pc = P.banded_ilu0(op)
    n = pc.n
    lu = np.eye(n, dtype=np.float64)

    def dense(bands, offsets, unit):
        a = np.zeros((n, n))
        for d, off in enumerate(offsets):
            for i in range(n):
                j = i + off
                if 0 <= j < n:
                    a[i, j] = float(bands[d, i])
        if unit:
            np.fill_diagonal(a, 1.0)
        return a

    l = dense(np.asarray(pc.l_bands), pc.l_offsets, unit=True)
    u = dense(np.asarray(pc.u_bands), pc.u_offsets, unit=False)
    resid = l @ u - _dense_of(op)
    # Zero on the stencil pattern itself; the dropped fill-in is bounded.
    for d, off in enumerate(op.offsets):
        on_pattern = np.diagonal(resid, offset=int(off))
        np.testing.assert_allclose(on_pattern, 0.0, atol=5e-5)
    assert np.abs(resid).max() < 0.5


def _tri_bands(key, n, offs):
    """Random triangular bands, a dominant diagonal where offs holds 0,
    out-of-range entries zeroed."""
    bands = jax.random.uniform(key, (len(offs), n), minval=0.2, maxval=1.0)
    if 0 in offs:
        bands = bands.at[offs.index(0)].add(2.0)
    return trisolve._mask_oob(bands, offs)


def _dense_tri(bands, offs, n, unit):
    a = np.eye(n) if unit else np.zeros((n, n))
    rows = np.arange(n)
    for d, off in enumerate(offs):
        keep = (rows + off >= 0) & (rows + off < n)
        a[rows[keep], rows[keep] + off] = np.asarray(bands[d],
                                                     np.float64)[keep]
    return a


@pytest.mark.parametrize("ny", [4, 9])      # grid lines 1024 wide
@pytest.mark.parametrize("lower,unit", [(True, True), (True, False),
                                        (False, False)])
def test_trisweep_kernel_matches_ref(lower, unit, ny):
    n = 1024 * ny
    offs = (-1024, -1, 0) if lower else (0, 1, 1024)
    if unit:
        offs = offs[:-1]
    bands = _tri_bands(jax.random.PRNGKey(7), n, offs)
    v = jax.random.normal(jax.random.PRNGKey(8), (n,))
    ref = trisolve.banded_trisweep_ref(bands, v, offs, unit_diag=unit,
                                       lower=lower)
    ker = trisolve.banded_trisweep_kernel(bands, v, offs, unit_diag=unit,
                                          lower=lower, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("near", [True, False])
@pytest.mark.parametrize("lower,unit", [(True, True), (True, False),
                                        (False, False)])
def test_trisweep_kernel_matches_dense_f64(lower, unit, near):
    """A ragged n (2.5 blocks): the kernel against the reference and a
    float64 dense triangular solve, with and without the +-1 band."""
    n = 2560
    offs = (-1024, -1, 0) if lower else (0, 1, 1024)
    offs = tuple(o for o in offs
                 if (near or abs(o) != 1) and (not unit or o != 0))
    bands = _tri_bands(jax.random.PRNGKey(9), n, offs)
    v = jax.random.normal(jax.random.PRNGKey(10), (n,))
    ker = np.asarray(trisolve.banded_trisweep_kernel(
        bands, v, offs, unit_diag=unit, lower=lower, interpret=True))
    ref = trisolve.banded_trisweep_ref(bands, v, offs, unit_diag=unit,
                                       lower=lower)
    exact = np.linalg.solve(_dense_tri(bands, offs, n, unit),
                            np.asarray(v, np.float64))
    np.testing.assert_allclose(ker, np.asarray(ref), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ker, exact, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lower", [True, False])
def test_trisweep_kernel_no_far_band(lower):
    """line_jacobi's factors: only the +-1 band, so any n engages."""
    n = 3000
    offs = (-1,) if lower else (0, 1)
    bands = _tri_bands(jax.random.PRNGKey(11), n, offs)
    v = jax.random.normal(jax.random.PRNGKey(12), (n,))
    ker = trisolve.banded_trisweep_kernel(bands, v, offs, unit_diag=lower,
                                          lower=lower, interpret=True)
    exact = np.linalg.solve(_dense_tri(bands, offs, n, lower),
                            np.asarray(v, np.float64))
    np.testing.assert_allclose(np.asarray(ker), exact, rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("reach", [2, 16, 200])
def test_trisweep_dispatch_unaligned_far_band_takes_ref(reach):
    n = 600
    offs = (-reach, -1)
    bands = _tri_bands(jax.random.PRNGKey(13), n, offs)
    v = jax.random.normal(jax.random.PRNGKey(14), (n,))
    before = collections.Counter(trisolve.DISPATCH)
    z = trisolve.banded_trisweep(bands, v, offs, unit_diag=True, lower=True)
    got = trisolve.DISPATCH - before
    assert got == collections.Counter({("ref", "far_band_unaligned"): 1})
    ref = trisolve.banded_trisweep_ref(bands, v, offs, unit_diag=True,
                                       lower=True)
    np.testing.assert_array_equal(np.asarray(z), np.asarray(ref))


def test_fused_cheb_kernel_matches_recurrence():
    op = stencils.poisson_2d(8)
    pc = P.chebyshev(op, order=5)
    v = jax.random.normal(jax.random.PRNGKey(3), (pc.n,))
    ref = pc._apply_ref(v, op)
    ker = matrix_powers.banded_cheb_apply(op.bands, v, op.offsets,
                                          theta=pc.theta, delta=pc.delta,
                                          rhos=pc.rhos, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_shifted_banded_powers_matches_ref():
    op = stencils.poisson_2d(8)
    n, s = 64, 4
    x = jax.random.normal(jax.random.PRNGKey(4), (n,))
    shifts = jnp.asarray([0.9, 4.1, 2.2, 6.6], jnp.float32)
    u_k, sg_k = matrix_powers.banded_powers(op.bands, x, op.offsets, s,
                                            shifts=shifts, interpret=True)
    u_r, sg_r = matrix_powers.matrix_powers_ref(op, x, s, eps=1e-30,
                                                shifts=shifts)
    np.testing.assert_allclose(np.asarray(u_k), np.asarray(u_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(sg_k), np.asarray(sg_r),
                               rtol=3e-4)


def test_ell_powers_matches_ref():
    op = stencils.poisson_2d(8, fmt="ell")
    n, s = 64, 4
    x = jax.random.normal(jax.random.PRNGKey(5), (n,))
    u_k, sg_k = matrix_powers.ell_powers(op.values, op.cols, x, s,
                                         interpret=True)
    u_r, sg_r = matrix_powers.matrix_powers_ref(op, x, s, eps=1e-30)
    np.testing.assert_allclose(np.asarray(u_k), np.asarray(u_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(sg_k), np.asarray(sg_r),
                               rtol=3e-4)


# --------------------------------------------------------------------------
# 2. the spectral interval must bound the spectrum from ABOVE
# --------------------------------------------------------------------------
@pytest.mark.parametrize("make", [lambda: stencils.poisson_2d(8),
                                  lambda: stencils.convection_diffusion_2d(8)])
def test_estimate_interval_upper_bounds_spectrum(make):
    op = make()
    lam_min, lam_max = P.estimate_interval(op)
    eigs = np.linalg.eigvals(_dense_of(op).astype(np.float64))
    assert lam_max >= float(eigs.real.max()) - 1e-4, (
        "lam_max below the true spectrum: Chebyshev would go indefinite")
    assert 0.0 < lam_min < lam_max


# --------------------------------------------------------------------------
# 3. parity: same solution, strictly fewer restarts
# --------------------------------------------------------------------------
STENCILS = {"poisson": lambda: stencils.poisson_2d(8),
            "convdiff": lambda: stencils.convection_diffusion_2d(8)}
PC = {"chebyshev": lambda op: P.chebyshev(op, order=4),
      "banded_ilu0": P.banded_ilu0,
      "line_jacobi": P.line_jacobi}


@pytest.mark.parametrize("stencil", sorted(STENCILS))
@pytest.mark.parametrize("pcname", sorted(PC))
def test_gmres_parity_fewer_restarts(stencil, pcname):
    op = STENCILS[stencil]()
    n = op.shape[0]
    b = jnp.sin(jnp.arange(n) * 0.37)
    plain = gmres(op, b, m=16, tol=1e-5, max_restarts=100)
    pc = PC[pcname](op)
    res = gmres(op, b, m=16, tol=1e-5, max_restarts=100, precond=pc)
    assert bool(plain.converged) and bool(res.converged)
    assert _rel_err(res.x, plain.x) < 1e-3
    assert int(res.restarts) < int(plain.restarts), (
        f"{pcname} on {stencil}: {int(res.restarts)} vs "
        f"{int(plain.restarts)} restarts")


@pytest.mark.parametrize("stencil", sorted(STENCILS))
@pytest.mark.parametrize("pcname", ["chebyshev", "banded_ilu0"])
def test_sstep_parity_fewer_restarts(stencil, pcname):
    op = STENCILS[stencil]()
    n = op.shape[0]
    b = jnp.sin(jnp.arange(n) * 0.37)
    plain = gmres_sstep(op, b, s=4, blocks=4, tol=1e-5, max_restarts=60)
    pc = PC[pcname](op)
    res = gmres_sstep(op, b, s=4, blocks=4, tol=1e-5, max_restarts=60,
                      precond=pc)
    assert bool(plain.converged) and bool(res.converged)
    assert _rel_err(res.x, plain.x) < 1e-3
    assert int(res.restarts) < int(plain.restarts)


def test_sstep_newton_basis_matches_monomial():
    op = stencils.poisson_2d(8)
    b = jnp.sin(jnp.arange(64) * 0.37)
    mono = gmres_sstep(op, b, s=4, blocks=4, tol=1e-5, max_restarts=60)
    newt = gmres_sstep(op, b, s=4, blocks=4, tol=1e-5, max_restarts=60,
                       basis="newton")
    assert bool(newt.converged)
    assert _rel_err(newt.x, mono.x) < 1e-3


def test_pipelined_gs_composes_with_precond():
    op = stencils.poisson_2d(8)
    b = jnp.sin(jnp.arange(64) * 0.37)
    pc = P.chebyshev(op, order=4)
    split = gmres(op, b, m=16, tol=1e-5, max_restarts=60, precond=pc)
    piped = gmres(op, b, m=16, tol=1e-5, max_restarts=60, precond=pc,
                  gs="cgs2_pipelined")
    assert bool(piped.converged)
    assert _rel_err(piped.x, split.x) < 1e-3
    assert int(piped.restarts) == int(split.restarts)


def test_self_healing_composes_with_precond():
    from repro.core.recovery import gmres_self_healing
    op = stencils.poisson_2d(8)
    b = jnp.sin(jnp.arange(64) * 0.37)
    plain, _ = gmres_self_healing(op, b, m=16, tol=1e-5, max_restarts=60)
    res, report = gmres_self_healing(op, b, m=16, tol=1e-5, max_restarts=60,
                                     precond=P.chebyshev(op, order=4))
    assert bool(res.converged)
    assert int(res.restarts) < int(plain.restarts)


def test_batched_precond_fewer_restarts():
    op = stencils.poisson_2d(8)
    bs = jax.random.normal(jax.random.PRNGKey(2), (3, 64))
    plain = gmres_batched(op, bs, m=16, tol=1e-4, max_restarts=80)
    pc = P.chebyshev(op, order=4)
    res = gmres_batched(op, bs, m=16, tol=1e-4, max_restarts=80, precond=pc)
    assert bool(res.converged.all())
    assert _rel_err(res.x, plain.x) < 1e-2
    assert int(np.max(np.asarray(res.restarts))) < int(
        np.max(np.asarray(plain.restarts)))


# --------------------------------------------------------------------------
# 4. scale invariance (PR 3 contract): c*A x = c*b has the SAME trajectory
# --------------------------------------------------------------------------
@pytest.mark.parametrize("c", [1e-6, 1e6])
@pytest.mark.parametrize("pcname", ["chebyshev", "banded_ilu0"])
def test_precond_scale_invariant(c, pcname):
    op = stencils.poisson_2d(8)
    b = jnp.sin(jnp.arange(64) * 0.37)
    sop = operators.BandedOperator(op.bands * c, op.offsets)
    ref = gmres(op, b, m=16, tol=1e-5, max_restarts=60,
                precond=PC[pcname](op))
    res = gmres(sop, b * c, m=16, tol=1e-5, max_restarts=60,
                precond=PC[pcname](sop))
    assert bool(jnp.isfinite(res.x).all()), f"non-finite x at c={c}"
    assert bool(res.converged)
    assert _rel_err(res.x, ref.x) < 1e-3
    assert int(res.restarts) == int(ref.restarts)


# --------------------------------------------------------------------------
# 5. every public solver honors precond= or raises a clear ValueError
# --------------------------------------------------------------------------
def test_every_public_solver_takes_precond():
    from repro.core.distributed import gmres_sharded, gmres_sstep_sharded
    from repro.core.recovery import gmres_self_healing
    for fn in (gmres, gmres_batched, gmres_batched_cycle, gmres_sstep,
               gmres_sharded, gmres_sstep_sharded, gmres_self_healing):
        assert "precond" in inspect.signature(fn).parameters, fn.__name__


@pytest.mark.parametrize("call", [
    lambda op, b, pc: gmres(op, b, m=8, precond=pc),
    lambda op, b, pc: gmres_sstep(op, b, s=2, blocks=4, precond=pc),
    lambda op, b, pc: gmres_batched(op, b[None, :], m=8, precond=pc),
])
def test_non_callable_precond_raises(call):
    op = stencils.poisson_2d(4)
    b = jnp.ones((16,))
    with pytest.raises(ValueError, match="precond must be callable"):
        call(op, b, "chebyshev")


def test_sharded_rejects_unknown_and_unshardable():
    from repro.compat import make_mesh
    from repro.core.distributed import gmres_sharded
    mesh = make_mesh((1,), ("model",))
    op = stencils.poisson_2d(4)
    b = jnp.ones((16,))
    with pytest.raises(ValueError, match="precond"):
        gmres_sharded(mesh, "model", op, b, m=8, precond="nonsense")
    with pytest.raises(ValueError, match="not shard-aware"):
        gmres_sharded(mesh, "model", op, b, m=8,
                      precond=P.banded_ilu0(op))


def test_sstep_unknown_basis_raises():
    op = stencils.poisson_2d(4)
    with pytest.raises(ValueError, match="basis"):
        gmres_sstep(op, jnp.ones((16,)), s=2, blocks=2, basis="legendre")


# --------------------------------------------------------------------------
# 6. dispatch spies + forced VMEM overflow
# --------------------------------------------------------------------------
def test_cheb_kernel_engages_and_overflow_degrades(monkeypatch):
    op = stencils.poisson_2d(8)
    b = jnp.sin(jnp.arange(64) * 0.37)
    calls = []
    orig = matrix_powers.banded_cheb_apply

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(matrix_powers, "banded_cheb_apply", spy)
    pc = P.chebyshev(op, order=4)
    res_k = gmres(op, b, m=16, tol=1e-5, max_restarts=60, precond=pc)
    assert bool(res_k.converged)
    assert calls, "fused Chebyshev kernel never engaged"

    def boom(*a, **k):
        raise AssertionError("kernel path taken despite forced overflow")

    monkeypatch.setattr(matrix_powers, "banded_cheb_apply", boom)
    monkeypatch.setattr(tuning, "cheb_fits", lambda *a, **k: False)
    res_r = gmres(op, b, m=16, tol=1e-5, max_restarts=60,
                  precond=P.chebyshev(op, order=4))
    assert bool(res_r.converged)
    assert _rel_err(res_r.x, res_k.x) < 1e-4
    assert int(res_r.restarts) == int(res_k.restarts)


def test_trisweep_kernel_engages_and_overflow_degrades(monkeypatch):
    op = stencils.poisson_2d(1024, 4)       # four grid lines 1024 wide
    b = jnp.sin(jnp.arange(4096) * 0.37)
    calls = []
    orig = trisolve.banded_trisweep_kernel

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(trisolve, "banded_trisweep_kernel", spy)
    res_k = gmres(op, b, m=16, tol=1e-5, max_restarts=60,
                  precond=P.banded_ilu0(op))
    assert bool(res_k.converged)
    assert calls, "trisweep kernel never engaged"

    def boom(*a, **k):
        raise AssertionError("kernel path taken despite forced overflow")

    monkeypatch.setattr(trisolve, "banded_trisweep_kernel", boom)
    monkeypatch.setattr(tuning, "trisweep_fits", lambda *a, **k: False)
    before = collections.Counter(trisolve.DISPATCH)
    res_r = gmres(op, b, m=16, tol=1e-5, max_restarts=60,
                  precond=P.banded_ilu0(op))
    assert (trisolve.DISPATCH - before).keys() == {("ref", "vmem")}
    assert bool(res_r.converged)
    assert _rel_err(res_r.x, res_k.x) < 1e-4
    assert int(res_r.restarts) == int(res_k.restarts)


def _bench_stencil():
    path = os.path.join(os.path.dirname(__file__), "..", "bench", "configs",
                        "banded_stencil.py")
    spec = importlib.util.spec_from_file_location("banded_stencil", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ilu0_kernel_parity_on_convection_diffusion_steps():
    """The benchmark's backward-Euler convection-diffusion bands on a grid
    1024 wide: the ILU(0) apply through the kernel matches the reference,
    and a solve through each takes the same steps and restarts."""
    fam = _bench_stencil()
    cfg = {"nx": 1024, "ny": 8, "beta": [0.5, 0.25], "tau": 4.0}
    op = fam.program_operator(cfg, {"bands": fam._bands(cfg)})
    b = fam.smooth_fields({**cfg, "initial": {"modes": 8, "pattern": 0}},
                          jax.random.PRNGKey(0), 1)[0]
    pc = P.banded_ilu0(op)
    before = collections.Counter(trisolve.DISPATCH)
    z_k = pc(b)
    assert (trisolve.DISPATCH - before) == collections.Counter(
        {("kernel", "far_band"): 2})
    with tuning.force_kernel_mode("ref"):
        z_r = pc(b)
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r), rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(z_r))))
    kw = dict(m=30, tol=1e-5, max_restarts=50, gs="cgs2_pipelined")
    res_k = gmres(op, b, b, precond=P.banded_ilu0(op), **kw)
    with tuning.force_kernel_mode("ref"):
        res_r = gmres(op, b, b, precond=P.banded_ilu0(op), **kw)
    assert bool(res_k.converged) and bool(res_r.converged)
    assert int(res_k.inner_steps) == int(res_r.inner_steps)
    assert int(res_k.restarts) == int(res_r.restarts)


def test_ell_powers_engages_and_overflow_degrades(monkeypatch):
    op = stencils.poisson_2d(8, fmt="ell")
    b = jnp.sin(jnp.arange(64) * 0.37)
    calls = []
    orig = matrix_powers.ell_powers

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(matrix_powers, "ell_powers", spy)
    res_k = gmres_sstep(op, b, s=4, blocks=4, tol=1e-5, max_restarts=60)
    assert bool(res_k.converged)
    assert calls, "ELL matrix-powers kernel never engaged"

    def boom(*a, **k):
        raise AssertionError("kernel path taken despite forced overflow")

    monkeypatch.setattr(matrix_powers, "ell_powers", boom)
    monkeypatch.setattr(tuning, "ell_powers_fits", lambda *a, **k: False)
    res_r = gmres_sstep(op, b, s=4, blocks=4, tol=1e-5, max_restarts=60)
    assert bool(res_r.converged)
    assert _rel_err(res_r.x, res_k.x) < 1e-3


# --------------------------------------------------------------------------
# 7. serve admission: mismatch refused with the field named
# --------------------------------------------------------------------------
def test_serve_rejects_precond_mismatch():
    from repro.serve.request import AdmissionError, validate_params
    from repro.serve.server import SolverServer
    op = stencils.poisson_2d(8)
    wrong_n = P.banded_ilu0(stencils.poisson_2d(4))
    with pytest.raises(AdmissionError, match=r"precond .* has n=16"):
        SolverServer(op, m=10, k=4, precond=wrong_n)
    dense_only = P.block_jacobi(jnp.eye(64) * 4.0, block=8)
    with pytest.raises(AdmissionError,
                       match="precond .* requires a dense operator"):
        SolverServer(op, m=10, k=4, precond=dense_only)
    with pytest.raises(AdmissionError, match="precond is not callable"):
        validate_params(1e-5, 10, precond=42, op=op)
    # The matching pairing sails through.
    validate_params(1e-5, 10, precond=P.banded_ilu0(op), op=op)


def test_serve_precond_cuts_restarts():
    from repro.serve.server import SolverServer
    op = stencils.poisson_2d(8)
    b = np.sin(np.arange(64) * 0.37).astype(np.float32)
    outs = {}
    for name, pc in (("none", None), ("cheb", P.chebyshev(op, order=4))):
        srv = SolverServer(op, m=10, k=4, precond=pc)
        rid = srv.submit(b, tol=1e-4, max_restarts=80)
        srv.run()
        outs[name] = srv.results[rid]
    assert outs["cheb"].status == "done"
    assert outs["cheb"].restarts < outs["none"].restarts
    r = np.linalg.norm(np.asarray(op(jnp.asarray(outs["cheb"].x))) - b)
    assert r / np.linalg.norm(b) < 1e-3


# --------------------------------------------------------------------------
# 8. hypothesis: random SPD stencil x precond x fmt -> dense-oracle match
# --------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
    _HYP = True
    settings.register_profile("ci", max_examples=25, deadline=None)
    settings.load_profile("ci")
except ImportError:                     # plain-pytest fallback: fixed grid
    _HYP = False

    def given(**kw):                    # noqa: D103 - deterministic sweep
        def deco(fn):
            cases = [(0, 16, 1, "jacobi", "banded"),
                     (1, 24, 2, "chebyshev", "dense"),
                     (2, 33, 1, "banded_ilu0", "banded"),
                     (3, 48, 2, "chebyshev", "ell")]

            @pytest.mark.parametrize("seed,n,halo,pcname,fmt", cases)
            def wrapped(seed, n, halo, pcname, fmt):
                return fn(seed=seed, n=n, halo=halo, pcname=pcname, fmt=fmt)
            return wrapped
        return deco

    class settings:                     # noqa: N801 - decorator stub
        def __init__(self, **kw): pass
        def __call__(self, fn): return fn


@given(**({"seed": st.integers(0, 10_000), "n": st.integers(16, 48),
           "halo": st.integers(1, 2),
           "pcname": st.sampled_from(["jacobi", "chebyshev",
                                      "banded_ilu0"]),
           "fmt": st.sampled_from(["banded", "dense", "ell"])}
          if _HYP else {}))
@settings(max_examples=25, deadline=None)
def test_random_stencil_precond_matches_dense_oracle(seed, n, halo, pcname,
                                                     fmt):
    bop = _sym_banded(jax.random.PRNGKey(seed), n, halo=halo)
    if pcname == "banded_ilu0":
        fmt = "banded"             # requires the band pattern
    if fmt == "banded":
        op = bop
    elif fmt == "ell":
        op = bop.to_ell()
    else:
        op = operators.DenseOperator(bop.todense())
    pc = (P.banded_ilu0(bop) if pcname == "banded_ilu0"
          else P.make_preconditioner(pcname, op))
    b = jax.random.normal(jax.random.PRNGKey(seed + 1), (n,))
    res = gmres(op, b, m=min(16, n - 2), tol=1e-5, max_restarts=80,
                precond=pc)
    oracle = np.linalg.solve(_dense_of(bop).astype(np.float64),
                             np.asarray(b, np.float64))
    assert bool(res.converged)
    assert _rel_err(res.x, oracle) < 1e-2


# --------------------------------------------------------------------------
# 9. sharded composition on 4 fake devices (subprocess)
# --------------------------------------------------------------------------
def _run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_precond_matches_oracle_and_one_psum_4dev():
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.compat import make_mesh
        from repro.core import gmres, gmres_sharded, stencils
        from repro.core.distributed import gmres_sstep_sharded
        from repro.roofline import innermost_loop_collectives
        mesh = make_mesh((4,), ('model',))
        op = stencils.poisson_2d(16)          # n=256, halo=16
        b = jnp.sin(jnp.arange(256) * 0.37)
        oracle = gmres(op, b, m=16, tol=1e-4, max_restarts=80)
        out = {"oracle_restarts": int(oracle.restarts)}
        for tag, pc in (("none", None), ("cheb", "chebyshev"),
                        ("bbj", "banded_block_jacobi")):
            jsol = jax.jit(lambda bb, pc=pc: gmres_sharded(
                mesh, 'model', op, bb, m=16, tol=1e-4, max_restarts=80,
                gs='cgs2_pipelined', precond=pc))
            hlo = jsol.lower(b).compile().as_text()
            _, ops = innermost_loop_collectives(hlo)
            r = jsol(b)
            out["restarts_" + tag] = int(r.restarts)
            out["conv_" + tag] = bool(r.converged)
            out["err_" + tag] = float(jnp.linalg.norm(r.x - oracle.x)
                                      / jnp.linalg.norm(oracle.x))
            out["psums_" + tag] = sum(o.count for o in ops
                                      if o.kind == "all-reduce")
        rs = gmres_sstep_sharded(mesh, 'model', op, b, s=4, blocks=4,
                                 tol=1e-4, max_restarts=60,
                                 precond='chebyshev')
        out["sstep_conv"] = bool(rs.converged)
        out["sstep_err"] = float(jnp.linalg.norm(rs.x - oracle.x)
                                 / jnp.linalg.norm(oracle.x))
        print(json.dumps(out))
    """)
    r = _run_subprocess(code)
    assert r["conv_none"] and r["conv_cheb"] and r["conv_bbj"]
    for tag in ("cheb", "bbj"):
        assert r["err_" + tag] < 1e-2
        assert r["restarts_" + tag] < r["restarts_none"]
        # Preconditioning must not add collectives to the inner loop:
        # Chebyshev rides the halo-exchange ppermutes, block-Jacobi is
        # shard-local — the pipelined one-psum-per-step schedule holds.
        assert r["psums_" + tag] <= r["psums_none"]
    assert r["psums_none"] >= 1
    assert r["sstep_conv"] and r["sstep_err"] < 1e-2
