"""Sparse/structured operator subsystem vs dense reference (Pallas interpret).

Mirrors tests/test_fused_solver.py for the SpMV layer: the ELL gather
kernel and the banded stencil kernel (kernels/spmv.py), the
``SparseOperator`` / ``BandedOperator`` dispatch (core/operators.py), the
stencil constructors (core/stencils.py), and the solver end-to-end —
``gmres`` / ``gmres_batched`` on 2-D/3-D Poisson and convection-diffusion
through ``backend="pallas"``.  On CPU ``kernels.tuning.kernel_mode()``
returns "interpret", so every kernel assertion here exercises the REAL
kernel arithmetic through the Pallas interpreter.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gmres, gmres_batched, stencils
from repro.core.operators import (BandedOperator, DenseOperator,
                                  SparseOperator)
from repro.kernels import spmv, tuning

KEY = jax.random.PRNGKey(0)


def _random_ell(n, width, seed=0, dtype=jnp.float32):
    values = jax.random.normal(jax.random.PRNGKey(seed), (n, width),
                               ).astype(dtype)
    cols = jax.random.randint(jax.random.PRNGKey(seed + 1), (n, width), 0, n)
    return values, cols.astype(jnp.int32)


def relres(a, x, b):
    return float(jnp.linalg.norm(a @ x - b) / jnp.linalg.norm(b))


# --------------------------------------------------------------------------
# ELL gather kernel vs the jnp oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,width,bm", [
    (256, 5, 128),
    (300, 7, 128),      # padding path (n not a block multiple)
    (96, 3, 256),       # block larger than the matrix
])
def test_ell_kernel_matches_reference(n, width, bm):
    values, cols = _random_ell(n, width)
    x = jax.random.normal(jax.random.PRNGKey(2), (n,))
    y_k = spmv.ell_matvec(values, cols, x, block_m=bm, interpret=True)
    y_r = spmv.ell_matvec_ref(values, cols, x)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=3e-5, atol=3e-5)


def test_ell_kernel_multi_rhs():
    values, cols = _random_ell(200, 4, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(5), (200, 6))
    y_k = spmv.ell_matvec(values, cols, x, block_m=64, interpret=True)
    y_r = spmv.ell_matvec_ref(values, cols, x)
    assert y_k.shape == (200, 6)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=3e-5, atol=3e-5)


def test_ell_kernel_bf16_values():
    """bf16 matrix storage, f32 operand: f32 accumulation in-kernel."""
    values, cols = _random_ell(160, 5, seed=7, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(9), (160,))
    y_k = spmv.ell_matvec(values, cols, x, block_m=64, interpret=True)
    y_r = spmv.ell_matvec_ref(values, cols, x)
    assert y_k.dtype == jnp.float32         # promoted, matches dense a @ x
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-2, atol=2e-2)


def test_ell_kernel_validates_shapes():
    values, cols = _random_ell(64, 3)
    with pytest.raises(TypeError):
        spmv.ell_matvec(values, cols, jnp.zeros((65,)), interpret=True)
    with pytest.raises(TypeError):
        spmv.ell_matvec(values, cols[:32], jnp.zeros((64,)), interpret=True)


# --------------------------------------------------------------------------
# banded/stencil kernel vs the jnp oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,offsets,bm", [
    (256, (-16, -1, 0, 1, 16), 128),
    (300, (-20, -1, 0, 1, 20), 128),    # padding path
    (90, (-30, -9, -1, 0, 1, 9, 30), 128),  # 7-band, block > n
])
def test_banded_kernel_matches_reference(n, offsets, bm):
    bands = jax.random.normal(KEY, (len(offsets), n))
    x = jax.random.normal(jax.random.PRNGKey(2), (n,))
    y_k = spmv.banded_matvec(bands, x, offsets, block_m=bm, interpret=True)
    y_r = spmv.banded_matvec_ref(bands, x, offsets)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=3e-5, atol=3e-5)


def test_banded_kernel_multi_rhs():
    offsets = (-8, -1, 0, 1, 8)
    bands = jax.random.normal(KEY, (5, 128))
    x = jax.random.normal(jax.random.PRNGKey(3), (128, 4))
    y_k = spmv.banded_matvec(bands, x, offsets, block_m=64, interpret=True)
    y_r = spmv.banded_matvec_ref(bands, x, offsets)
    assert y_k.shape == (128, 4)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=3e-5, atol=3e-5)


def test_banded_kernel_validates_shapes():
    bands = jnp.ones((3, 64))
    with pytest.raises(TypeError):
        spmv.banded_matvec(bands, jnp.zeros((64,)), (-1, 0), interpret=True)
    with pytest.raises(TypeError):
        spmv.banded_matvec(bands, jnp.zeros((60,)), (-1, 0, 1),
                           interpret=True)


# --------------------------------------------------------------------------
# operators: matvec parity vs dense materialization, both backends
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_sparse_operator_matches_dense(backend):
    a = np.array(jax.random.normal(KEY, (150, 150)))
    a[np.abs(a) < 1.2] = 0.0               # sparsify, ragged row widths
    op = SparseOperator.from_dense(a, backend=backend)
    dense = np.asarray(op.todense())
    np.testing.assert_allclose(dense, a, rtol=1e-6, atol=1e-6)
    v = jax.random.normal(jax.random.PRNGKey(2), (150,))
    np.testing.assert_allclose(np.asarray(op(v)), a @ np.asarray(v),
                               rtol=3e-5, atol=3e-5)
    x = jax.random.normal(jax.random.PRNGKey(3), (150, 5))
    np.testing.assert_allclose(np.asarray(op(x)), a @ np.asarray(x),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_banded_operator_matches_dense(backend):
    op = stencils.convection_diffusion_2d(13, 11, beta=(0.7, 0.3),
                                          backend=backend)
    a = np.asarray(op.todense())
    v = jax.random.normal(jax.random.PRNGKey(4), (143,))
    np.testing.assert_allclose(np.asarray(op(v)), a @ np.asarray(v),
                               rtol=3e-5, atol=3e-5)
    x = jax.random.normal(jax.random.PRNGKey(5), (143, 3))
    np.testing.assert_allclose(np.asarray(op(x)), a @ np.asarray(x),
                               rtol=3e-5, atol=3e-5)


def test_banded_to_ell_same_matrix():
    band = stencils.poisson_2d(7, 9)
    ell = band.to_ell()
    np.testing.assert_allclose(np.asarray(band.todense()),
                               np.asarray(ell.todense()), atol=0)
    v = jax.random.normal(KEY, (63,))
    np.testing.assert_allclose(np.asarray(band(v)), np.asarray(ell(v)),
                               rtol=3e-5, atol=3e-5)


def test_from_dense_rejects_lossy_width():
    a = np.eye(8, dtype=np.float32)
    a[0, :] = 1.0                          # one row with 8 nonzeros
    with pytest.raises(ValueError):
        SparseOperator.from_dense(a, width=3)


def test_operator_pytrees_survive_roundtrip():
    sp = stencils.poisson_2d(6, fmt="ell", backend="pallas")
    leaves, treedef = jax.tree_util.tree_flatten(sp)
    sp2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert sp2.backend == "pallas"
    bd = stencils.poisson_2d(6, backend="pallas")
    leaves, treedef = jax.tree_util.tree_flatten(bd)
    bd2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert bd2.offsets == (-6, -1, 0, 1, 6) and bd2.backend == "pallas"


# --------------------------------------------------------------------------
# stencil constructors
# --------------------------------------------------------------------------
def test_poisson_2d_structure():
    nx, ny = 5, 4
    a = np.asarray(stencils.poisson_2d(nx, ny).todense())
    ref = np.zeros_like(a)
    for iy in range(ny):
        for ix in range(nx):
            i = ix + nx * iy
            ref[i, i] = 4
            for di, dj in ((1, 0), (-1, 0), (0, 1), (0, -1)):
                jx, jy = ix + di, iy + dj
                if 0 <= jx < nx and 0 <= jy < ny:
                    ref[i, jx + nx * jy] = -1
    np.testing.assert_allclose(a, ref, atol=0)


def test_poisson_3d_structure():
    nx, ny, nz = 3, 4, 3
    a = np.asarray(stencils.poisson_3d(nx, ny, nz).todense())
    np.testing.assert_allclose(a, a.T, atol=0)           # SPD stencil
    assert a.shape == (36, 36) and a[0, 0] == 6
    # interior row touches exactly 7 entries
    i = 1 + nx * (1 + ny * 1)
    assert int((a[i] != 0).sum()) == 7


def test_convection_diffusion_reduces_to_poisson():
    cd = stencils.convection_diffusion_2d(6, 5, beta=(0.0, 0.0))
    po = stencils.poisson_2d(6, 5)
    np.testing.assert_allclose(np.asarray(cd.todense()),
                               np.asarray(po.todense()), atol=0)
    a = np.asarray(stencils.convection_diffusion_2d(6, 5,
                                                    beta=(0.8, 0.2)).todense())
    assert np.abs(a - a.T).max() > 0       # convection breaks symmetry


# --------------------------------------------------------------------------
# solver end-to-end: sparse systems through the kernel path
# --------------------------------------------------------------------------
def test_gmres_sparse_poisson_pallas_converges():
    """The acceptance-criteria solve: 2-D Poisson, ELL, Pallas SpMV path."""
    op = stencils.poisson_2d(12, 12, fmt="ell", backend="pallas")
    b = jax.random.normal(jax.random.PRNGKey(1), (144,))
    res = gmres(op, b, m=30, tol=1e-6, max_restarts=200)
    assert bool(res.converged)
    a = op.todense()
    assert relres(a, res.x, b) < 5e-6
    # parity vs the jnp-reference sparse path AND the dense solve
    res_ref = gmres(stencils.poisson_2d(12, 12, fmt="ell"), b, m=30,
                    tol=1e-6, max_restarts=200)
    res_dense = gmres(a, b, m=30, tol=1e-6, max_restarts=200)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(res_ref.x),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(res_dense.x),
                               rtol=1e-4, atol=1e-5)


def test_gmres_banded_convection_diffusion_converges():
    op = stencils.convection_diffusion_2d(10, 10, beta=(0.6, 0.3),
                                          backend="pallas")
    b = jnp.ones((100,))
    res = gmres(op, b, m=30, tol=1e-6, max_restarts=200)
    assert bool(res.converged)
    assert relres(op.todense(), res.x, b) < 5e-6


def test_gmres_sparse_under_jit():
    op = stencils.poisson_2d(8, 8, fmt="ell", backend="pallas")
    b = jax.random.normal(jax.random.PRNGKey(3), (64,))
    res = jax.jit(lambda o, b: gmres(o, b, m=20, tol=1e-5,
                                     max_restarts=100))(op, b)
    assert bool(res.converged)


def test_gmres_fused_scheme_degrades_with_sparse_operator():
    """gs="fused" needs a DenseOperator; sparse degrades to cgs2_fused."""
    op = stencils.poisson_2d(8, 8, backend="pallas")
    b = jax.random.normal(jax.random.PRNGKey(5), (64,))
    res = gmres(op, b, m=20, tol=1e-5, max_restarts=100, gs="fused")
    assert bool(res.converged)
    assert relres(op.todense(), res.x, b) < 5e-5


def test_gmres_batched_sparse_matches_per_lane():
    op = stencils.poisson_2d(9, 9, fmt="ell", backend="pallas")
    bs = jax.random.normal(jax.random.PRNGKey(7), (3, 81))
    res = gmres_batched(op, bs, m=20, tol=1e-5, max_restarts=100)
    assert bool(res.converged.all())
    for i in range(3):
        single = gmres(op, bs[i], m=20, tol=1e-5, max_restarts=100)
        np.testing.assert_allclose(np.asarray(res.x[i]),
                                   np.asarray(single.x),
                                   rtol=1e-4, atol=1e-5)


def test_gmres_sparse_compute_dtype_bf16():
    op = stencils.poisson_2d(10, 10, fmt="ell", backend="pallas")
    b = jax.random.normal(jax.random.PRNGKey(9), (100,))
    res = gmres(op, b, m=25, tol=1e-4, max_restarts=200,
                compute_dtype=jnp.bfloat16)
    assert bool(res.converged)
    assert relres(op.todense(), res.x, b) < 5e-4


def test_sparse_operator_ref_env_override(monkeypatch):
    """REPRO_KERNELS=ref must force the jnp path (identical results)."""
    op = stencils.poisson_2d(6, 6, fmt="ell", backend="pallas")
    v = jax.random.normal(KEY, (36,))
    y_kernel = np.asarray(op(v))
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    y_ref = np.asarray(op(v))
    np.testing.assert_allclose(y_kernel, y_ref, rtol=3e-5, atol=3e-5)


# --------------------------------------------------------------------------
# tuning
# --------------------------------------------------------------------------
def test_choose_spmv_block_respects_budget():
    for (n, width, k) in [(1024, 5, 1), (65536, 7, 1), (16384, 9, 8)]:
        bm = tuning.choose_spmv_block(n, width, "float32", k=k)
        resident = tuning._round_up(n, tuning.LANE) * k * 4
        assert 2 * bm * width * 8 + resident + bm * k * 4 <= tuning.VMEM_BUDGET
        assert bm % tuning.sublane("float32") == 0 or bm >= n


def test_spmv_fits_rejects_vmem_overflow():
    # The resident (n, 1) operand pads its lane dim to 128 in VMEM (512
    # bytes a row): 8k rows fit the budget, 64k rows do not.
    assert tuning.spmv_fits(8192, 5, jnp.float32)
    assert not tuning.spmv_fits(65536, 5, jnp.float32)
    # an operand too large to sit in VMEM must push the op to the jnp path
    assert not tuning.spmv_fits(8_000_000, 5, jnp.float32)
    assert tuning.banded_fits(65536, 5, jnp.float32, halo=256)
    assert not tuning.banded_fits(8_000_000, 5, jnp.float32, halo=256)


def test_huge_sparse_operator_falls_back_to_jnp():
    """A pallas-backend op whose x exceeds VMEM still computes (jnp path)."""
    n = 8_000_000
    # don't materialize anything n-sized beyond the band vectors
    op = BandedOperator(jnp.stack([jnp.full((n,), 4.0),
                                   jnp.full((n,), -1.0)]),
                        (0, 1), backend="pallas")
    v = jnp.ones((n,))
    y = op(v)
    assert float(y[0]) == 3.0 and float(y[-1]) == 4.0
