"""The main path's kernels through the TPU v5e compiler, with no chip attached.

Interpret mode (what the rest of the suite runs) does not apply Mosaic's
layout and VMEM rules; these cases compile the real programs at their real
sizes for a described v5e chip, so a kernel the chip would refuse fails
here.  Nothing runs: a compile that passes is not a chip run.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import gmres
from repro.core.gmres import gmres_batched_cycle
from repro.core.operators import BandedOperator, DenseOperator
from repro.kernels import cgs2, trisolve, tuning


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _kernel_text(fn, *shapes):
    with tuning.force_kernel_mode("compiled"):
        return jax.jit(fn).lower(*shapes).compile().as_text()


def _f32(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_dense_gmres_fused_n10000(one_chip, no_compile_cache):
    n = 10_000
    text = _kernel_text(
        lambda a, b: gmres(DenseOperator(a, "pallas"), b, m=30, tol=1e-6,
                           gs="fused").x,
        _f32((n, n), one_chip), _f32((n,), one_chip))
    assert "tpu_custom_call" in text
    assert "gmres_arnoldi_fused" in text


def test_batched_cycle_k8(one_chip, no_compile_cache):
    n, k = 8192, 8
    text = _kernel_text(
        lambda a, b, x, t, act: gmres_batched_cycle(
            DenseOperator(a, "pallas"), b, x, m=30, tol_abs=t, active=act),
        _f32((n, n), one_chip), _f32((k, n), one_chip),
        _f32((k, n), one_chip), _f32((k,), one_chip),
        _f32((k,), one_chip, jnp.bool_))
    assert "tpu_custom_call" in text
    assert "gmres_block_gs_batched" in text


def test_banded_spmv_stencil_1024(one_chip, no_compile_cache):
    nx = 1024
    n = nx * nx
    offsets = (-nx, -1, 0, 1, nx)
    assert tuning.banded_fits(n, 5, jnp.float32, halo=nx)
    text = _kernel_text(
        lambda bands, x: BandedOperator(bands, offsets, "pallas")(x),
        _f32((5, n), one_chip), _f32((n,), one_chip))
    assert "tpu_custom_call" in text
    assert "gmres_spmv_banded" in text


@pytest.mark.parametrize("lower", [True, False])
def test_ilu0_trisweep_stencil_1024(lower, one_chip, no_compile_cache):
    """Both sweeps of the 1024^2 stencil's ILU(0), from the lane-dense
    factors a preconditioner keeps: the kernel, and no copy of the bands."""
    nx = 1024
    n = nx * nx
    offsets = (-nx, -1) if lower else (0, 1, nx)
    text = _kernel_text(
        lambda bands, tiles, v: trisolve.banded_trisweep(
            bands, v, offsets, unit_diag=lower, lower=lower, tiles=tiles),
        _f32((len(offsets), n), one_chip),
        _f32((len(offsets), n // 128, 128), one_chip),
        _f32((n,), one_chip))
    assert "gmres_precond_trisweep" in text
    assert " copy(" not in text


def test_cgs2_payload_kernel_2p20(one_chip, no_compile_cache):
    n, m1 = 1 << 20, 31
    text = _kernel_text(
        lambda v, w, mask: cgs2.gs_project_norm_partial(v, w, mask),
        _f32((m1, n), one_chip), _f32((n, 2), one_chip),
        _f32((m1,), one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["banded_powers", "banded_cheb_apply"])
def test_resident_banded_gate_agrees_with_compiler(kernel, one_chip,
                                                   no_compile_cache):
    """The grid-free banded kernels hold whole vectors in VMEM.  At the
    largest lane-aligned 2-D stencil their gate admits, the compiler takes
    the kernel; at the main path's 1024^2 the gate refuses."""
    from repro.core.preconditioners import cheb_coeffs
    from repro.kernels import matrix_powers

    f32 = jnp.float32
    if kernel == "banded_powers":
        fits = lambda nx: tuning.powers_fits(nx * nx, f32, 4, nbands=5,
                                             halo=nx)
        call = lambda bands, x, offs: matrix_powers.banded_powers(
            bands, x, offs, 4)[0]
    else:
        coeffs = dict(zip(("theta", "delta", "rhos"),
                          cheb_coeffs(4, 0.1, 8.0)))
        fits = lambda nx: tuning.cheb_fits(nx * nx, 5, f32, halo=nx)
        call = lambda bands, x, offs: matrix_powers.banded_cheb_apply(
            bands, x, offs, **coeffs)
    assert not fits(1024)
    nx = max(nx for nx in range(128, 1025, 128) if fits(nx))
    offs = (-nx, -1, 0, 1, nx)
    text = _kernel_text(lambda bands, x: call(bands, x, offs),
                        _f32((5, nx * nx), one_chip),
                        _f32((nx * nx,), one_chip))
    assert "tpu_custom_call" in text
