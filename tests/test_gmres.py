"""Core GMRES correctness: vs dense solve, vs NumPy oracle, all schemes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gmres, gmres_batched, operators, preconditioners
from repro.core.strategies import serial_numpy


def _system(n=160, seed=0, kind="diagdom"):
    key = jax.random.PRNGKey(seed)
    if kind == "diagdom":
        a = operators.random_diagdom(key, n)
    elif kind == "convdiff":
        a = operators.convection_diffusion(n, beta=0.4)
    else:
        a = operators.poisson_1d(n)
    b = jax.random.normal(jax.random.PRNGKey(seed + 1), (n,))
    return a, b


def relres(a, x, b):
    return float(jnp.linalg.norm(a @ x - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("gs", ["cgs", "mgs", "cgs2"])
@pytest.mark.parametrize("kind", ["diagdom", "convdiff", "poisson"])
def test_converges_all_schemes(gs, kind):
    # restarted GMRES stagnates on the (ill-conditioned SPD) Poisson matrix
    # — a known property, not a bug — so that case runs full-memory m=n
    # with an fp32-realistic tolerance.
    n = 96 if kind == "poisson" else 160
    m, tol = (96, 1e-4) if kind == "poisson" else (30, 1e-5)
    a, b = _system(n=n, kind=kind)
    res = jax.jit(lambda a, b: gmres(a, b, m=m, tol=tol, gs=gs,
                                     max_restarts=200))(a, b)
    assert bool(res.converged), (gs, kind, float(res.residual))
    assert relres(a, res.x, b) < 5 * tol


@pytest.mark.parametrize("gs", ["cgs2", "cgs2_pipelined"])
@pytest.mark.parametrize("exp", [-70, 60])
def test_tiny_and_huge_rhs_solve_as_unit_scale(gs, exp):
    """A right-hand side scaled by a power of two far from 1 (a decayed
    time-stepper state) takes the same steps and returns the unit-scale
    answer scaled exactly: no absolute guard or squared norm underflows."""
    a, b = _system(kind="convdiff")
    kw = dict(m=30, tol=1e-5, gs=gs, max_restarts=50)
    ref = gmres(a, b, **kw)
    res = gmres(a, b * 2.0 ** exp, **kw)
    assert bool(res.converged)
    assert int(res.inner_steps) == int(ref.inner_steps)
    np.testing.assert_array_equal(np.asarray(res.x),
                                  np.asarray(ref.x) * 2.0 ** exp)


def test_matches_dense_solve():
    a, b = _system()
    res = gmres(a, b, m=40, tol=1e-6, max_restarts=100)
    x_dense = jnp.linalg.solve(a, b)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_dense),
                               rtol=2e-3, atol=2e-4)


def test_matches_numpy_oracle():
    a, b = _system(n=120)
    res = gmres(a, b, m=20, tol=1e-5)
    x_np, beta, _, conv, _ = serial_numpy(np.asarray(a), np.asarray(b),
                                          m=20, tol=1e-5)
    assert conv
    np.testing.assert_allclose(np.asarray(res.x), x_np, rtol=5e-3, atol=5e-4)


def test_restart_counting_and_early_stop():
    # convection-diffusion with strong convection needs >5 Krylov dims
    a = operators.convection_diffusion(200, beta=0.9)
    b = jax.random.normal(jax.random.PRNGKey(1), (200,))
    res = gmres(a, b, m=5, tol=1e-5, max_restarts=200)
    assert bool(res.converged)
    assert int(res.restarts) > 1
    # already-converged x0 does nothing
    res2 = gmres(a, b, x0=res.x, m=5, tol=1e-5)
    assert int(res2.restarts) == 0
    assert int(res2.inner_steps) == 0


def test_early_convergence_masks_basis():
    """m far larger than needed: masked steps must not corrupt x."""
    a, b = _system(n=64)
    res = gmres(a, b, m=60, tol=1e-5)
    assert bool(res.converged)
    assert int(res.inner_steps) < 60
    assert relres(a, res.x, b) < 5e-5


def test_matrix_free_operator():
    a, b = _system()
    op = operators.FunctionOperator(lambda v, mat: mat @ v, a.shape[0],
                                    captures=(a,))
    res = gmres(op, b, m=30, tol=1e-5)
    assert bool(res.converged)
    assert relres(a, res.x, b) < 5e-5


def test_batched_rhs():
    a, _ = _system()
    bs = jax.random.normal(jax.random.PRNGKey(7), (5, a.shape[0]))
    res = gmres_batched(a, bs, m=30, tol=1e-5)
    assert bool(res.converged.all())
    for i in range(5):
        assert relres(a, res.x[i], bs[i]) < 5e-5


def test_batched_mixed_tolerance_parity():
    """Per-lane tol/budget arrays: every lane must stop on ITS OWN
    contract — same restarts and solution as a standalone gmres with that
    tol — and a loose lane must burn fewer cycles than a tight one."""
    # Convection-diffusion needs tens of restarts at m=10, so the four
    # tolerances land on genuinely different restart counts (~20/33/49/24).
    a, _ = _system(kind="convdiff")
    n = a.shape[0]
    bs = jax.random.normal(jax.random.PRNGKey(11), (4, n))
    tols = jnp.array([1e-2, 1e-4, 1e-6, 1e-3])
    budgets = jnp.array([80, 80, 80, 80])
    res = gmres_batched(a, bs, m=10, tol=tols, max_restarts=budgets)
    assert bool(res.converged.all()) and bool(res.done.all())
    for i in range(4):
        tol = float(tols[i])
        ref = gmres(a, bs[i], m=10, tol=tol, max_restarts=80)
        # +-1: block and scalar cycles round differently at fp32, the
        # same residual-parity contract the pipelined scheme tests use.
        assert abs(int(res.restarts[i]) - int(ref.restarts)) <= 1, i
        # The solver's own residual meets the lane tol exactly; the
        # independent recomputation here gets fp32 matmul slack.
        bnorm = float(jnp.linalg.norm(bs[i]))
        assert float(res.residual[i]) <= tol * bnorm * (1 + 1e-6)
        assert relres(a, res.x[i], bs[i]) <= 2 * tol
        np.testing.assert_allclose(np.asarray(res.x[i]), np.asarray(ref.x),
                                   rtol=5e-2, atol=5e-3)
    # The mixed block really is heterogeneous: loose < tight lane cost.
    assert int(res.restarts[0]) < int(res.restarts[2])
    assert int(res.inner_steps[0]) < int(res.inner_steps[2])


def test_batched_per_lane_budget_failed_lane_flagged():
    """A lane out of budget reports done=True / converged=False (the
    FAILED retirement signal) without disturbing its cohort."""
    a, _ = _system()
    bs = jax.random.normal(jax.random.PRNGKey(12), (3, a.shape[0]))
    res = gmres_batched(a, bs, m=4, tol=jnp.array([1e-5, 1e-14, 1e-5]),
                        max_restarts=jnp.array([50, 2, 50]))
    assert bool(res.done.all())
    assert bool(res.converged[0]) and bool(res.converged[2])
    assert not bool(res.converged[1]) and int(res.restarts[1]) == 2
    for i in (0, 2):
        assert relres(a, res.x[i], bs[i]) < 5e-5


@pytest.mark.parametrize("precond", ["jacobi", "neumann", "block_jacobi"])
def test_preconditioners_cut_iterations(precond):
    a, b = _system(n=128, kind="diagdom")
    base = gmres(a, b, m=20, tol=1e-5, max_restarts=100)
    pc = preconditioners.PRECONDITIONERS[precond](a, block=32, order=2)
    res = gmres(a, b, m=20, tol=1e-5, max_restarts=100, precond=pc)
    assert bool(res.converged)
    assert relres(a, res.x, b) < 1e-4
    assert int(res.inner_steps) <= int(base.inner_steps)


def test_singular_direction_breakdown_is_safe():
    """Happy breakdown: b in a low-dim invariant subspace."""
    n = 64
    a = jnp.diag(jnp.arange(1.0, n + 1))
    b = jnp.zeros((n,)).at[3].set(1.0)   # eigvec -> 1-step convergence
    res = gmres(a, b, m=10, tol=1e-6)
    assert bool(res.converged)
    assert int(res.inner_steps) <= 2
    assert relres(a, res.x, b) < 1e-5


def test_jvp_operator_gauss_newton():
    """GMRES on a J^T J system via the matrix-free jvp operator."""
    key = jax.random.PRNGKey(3)
    w = jax.random.normal(key, (24,))

    def f(p):
        return jnp.tanh(p) * 2.0 - w

    op = operators.jvp_operator(f, w * 0.1, damping=0.1)
    g = jax.grad(lambda p: 0.5 * jnp.sum(f(p) ** 2))(w * 0.1)
    res = gmres(op, -g, m=24, tol=1e-5, max_restarts=10)
    assert bool(res.converged)
