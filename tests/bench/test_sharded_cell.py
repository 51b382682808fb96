"""A cell on several chips, on the CPU: the harness's sharded path and the
trace readings that stay true across chips.

The runs need four devices, which the CPU backend gives only when
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` is set before JAX
starts: they run once, in a child process (this file's ``__main__``), on a
root whose ``BENCHMARK.json`` defines its cells, and the tests read what
the child printed.  The readings run in this process on synthetic planes
and on the recorded one-chip trace.
"""
from __future__ import annotations

import contextlib
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, roofline, trace  # noqa: E402

SEED = 2**31 + 4242
# Each reading of the fake clock advances it by TICK_S, so a window of
# WINDOW_S holds the same number of steps on one chip and on four.
TICK_S = 1e-3
WINDOW_S = 0.04


# --------------------------------------------------------------------------
# A root with cells on four chips, defined only in its files
# --------------------------------------------------------------------------
def sharded_root(path: pathlib.Path, nx: int = 64, ny: int = 64
                 ) -> pathlib.Path:
    """Cells of the banded stencil on an ``nx`` by ``ny`` grid: plain and
    with block-Jacobi ILU(0) on four chips, plain on one; and two cells
    that cannot run on four chips (a dense family, an open loop)."""
    cfgs = path / "bench" / "configs"
    cfgs.mkdir(parents=True)
    (path / "bench" / "traffic").mkdir()
    conv = json.loads((ROOT / "bench/configs/convdiff2d_1024_be.json")
                      .read_text())
    conv.update(nx=nx, ny=ny)
    dense = json.loads((ROOT / "bench/configs/paper_dense_n10k.json")
                       .read_text())
    dense.update(n=256)
    (cfgs / "grid.json").write_text(json.dumps(conv))
    (cfgs / "dense.json").write_text(json.dumps(dense))
    plain = json.loads((ROOT / "bench/traffic/timestep_plain.json")
                       .read_text())
    (path / "bench/traffic/timestep_block_jacobi.json").write_text(
        json.dumps(dict(plain, precond="banded_block_jacobi")))
    (path / "bench/traffic/open4.json").write_text(json.dumps({
        "loop": "open", "rate": 10.0, "tol_loguniform": [1e-6, 1e-4],
        "pool": 2, "check_sample": 4}))
    cells = [("grid4.plain", "grid", "timestep_plain", 4),
             ("grid4.block_jacobi", "grid", "timestep_block_jacobi", 4),
             ("grid1.plain", "grid", "timestep_plain", 1),
             ("dense4.solve", "dense", "solve_loop", 4),
             ("grid4.open", "grid", "open4", 4)]
    steppers = [c[0] for c in cells if c[1] == "grid" and c[2] != "open4"]
    spec = {
        "configs": [
            {"name": "grid", "file": "bench/configs/grid.json"},
            {"name": "dense", "file": "bench/configs/dense.json"}],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": k}
                      for n, c, t, k in cells],
        "end_to_end": [
            {"name": "step_ms.plain", "unit": "ms", "better": "lower",
             "bound": 0.08, "source": "host_clock", "workloads": steppers},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [],
        "run_seconds": 1,
    }
    (path / "BENCHMARK.json").write_text(json.dumps(spec))
    return path


def _counting(module, name: str, counts: dict):
    """Replace ``module.name`` with a wrapper that counts its calls."""
    fn = getattr(module, name)

    def counted(*a, **k):
        counts[name] = counts.get(name, 0) + 1
        return fn(*a, **k)

    setattr(module, name, counted)
    return fn


def _runs(root: pathlib.Path) -> dict:
    """The child's runs: each cell's result, where its sampled answers
    lived, the plain cells' answers, and the block-Jacobi cell's counts of
    factorizations and solve traces."""
    import time

    import jax

    import repro.core as core
    from repro.kernels import trisolve, tuning

    assert len(jax.devices()) == 4, jax.devices()
    clock = [0.0]

    def now():
        clock[0] += TICK_S
        return clock[0]

    harness._now = now
    kept = {}
    check = harness.check_answers

    def keep_sample(host, sample, cfg):
        kept["sample"] = sample
        return check(host, sample, cfg)

    harness.check_answers = keep_sample

    def run(cell, answers=False):
        kept.clear()
        res = harness.run_cell(root, cell, SEED, WINDOW_S, False,
                               t_process=time.perf_counter(),
                               require_tpu=False,
                               counter=harness.CompileCounter())
        xs = [x for x, _, _ in kept["sample"]]
        if answers:
            res["answers"] = [np.asarray(x, np.float64).tolist()
                              for x in xs]
        res["x_devices"] = [len(x.sharding.device_set) for x in xs]
        res["x_replicated"] = [x.sharding.is_fully_replicated for x in xs]
        return res

    out = {"grid1.plain": run("grid1.plain", answers=True),
           "grid4.plain": run("grid4.plain", answers=True)}
    counts: dict = {}
    factor = _counting(trisolve, "banded_ilu0", counts)
    gmres = _counting(core, "gmres", counts)
    try:
        out["grid4.block_jacobi"] = run("grid4.block_jacobi")
    finally:
        trisolve.banded_ilu0, core.gmres = factor, gmres
    out["grid4.block_jacobi"]["setup_counts"] = counts
    # The halo exchange left out: the operator no longer knows it is one
    # shard of four, and each shard solves its own block alone.
    shard_context = tuning.shard_context
    tuning.shard_context = lambda *a: contextlib.nullcontext()
    try:
        out["grid4.no_halo"] = run("grid4.plain")
    finally:
        tuning.shard_context = shard_context
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = sharded_root(tmp_path_factory.mktemp("sharded"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env.pop("REPRO_KERNELS", None)
    out = subprocess.run([sys.executable, __file__, str(root)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# --------------------------------------------------------------------------
# Runs on four CPU devices
# --------------------------------------------------------------------------
@pytest.mark.parametrize("cell", ["grid4.plain", "grid4.block_jacobi"])
def test_four_chip_cell_is_correct(runs, cell):
    res = runs[cell]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"step_ms.plain", "setup_s"}
    assert res["window_compiles"] == 0


@pytest.mark.parametrize("cell", ["grid4.plain", "grid4.block_jacobi"])
def test_four_chip_answers_stay_sharded(runs, cell):
    res = runs[cell]
    assert res["x_devices"] and set(res["x_devices"]) == {4}
    assert not any(res["x_replicated"])


def test_four_chip_answers_match_one_chip(runs):
    """The same steps on one chip and on four: every sampled answer agrees
    within the solver's tolerance (relative, in the 2-norm)."""
    tol = json.loads((ROOT / "bench/configs/convdiff2d_1024_be.json")
                     .read_text())["solver"]["tol"]
    one, four = runs["grid1.plain"], runs["grid4.plain"]
    assert one["attempted"] == four["attempted"]
    assert len(one["answers"]) == len(four["answers"]) > 0
    for x1, x4 in zip(one["answers"], four["answers"]):
        x1, x4 = np.asarray(x1), np.asarray(x4)
        assert np.linalg.norm(x4 - x1) <= tol * np.linalg.norm(x1)


def test_preconditioner_is_factored_once(runs):
    """ILU(0) of each shard's block is factored in the set-up's one trace;
    the timed solve binds the factors and factors nothing."""
    counts = runs["grid4.block_jacobi"]["setup_counts"]
    assert counts["banded_ilu0"] == 1
    # gmres traced once for the warm-up, and never again in the window.
    assert counts["gmres"] == 1


def test_sharded_solve_without_halo_is_not_correct(runs):
    res = runs["grid4.no_halo"]
    check = res["checks"]["resid_over_tol_max"]
    assert not res["correct"] and check["value"] > check["limit"]


@pytest.mark.parametrize("cell, why", [
    ("dense4.solve", "shard_specs"), ("grid4.open", "closed loop")])
def test_cell_that_cannot_shard_fails_at_setup(tmp_path, cell, why):
    import time

    root = sharded_root(tmp_path)
    with pytest.raises(ValueError, match=why):
        harness.run_cell(root, cell, SEED, WINDOW_S, False,
                         t_process=time.perf_counter(), require_tpu=False)


# --------------------------------------------------------------------------
# Readings on several planes
# --------------------------------------------------------------------------
PEAKS = roofline.peaks("TPU v5 lite")


def _run_on(planes: dict, chips: int, window=(0.0, 1e6)):
    raw = {"devices": planes,
           "host": [["bench.window", window[0], window[1] - window[0]]]}
    return harness.Run(cell={"chips": chips}, config={}, traffic={}, seed=0,
                       seconds=1.0, shapes={}, peaks=PEAKS,
                       trace=trace.Summary(raw))


def _shard_ops(offset: float):
    """One shard's ops of a step: a mat-vec, a payload all-reduce half
    hidden under the next mat-vec, a halo exchange, all inside a loop."""
    return [["while.3", offset, 9e5],
            ["gmres_spmv_banded.1", offset + 1e5, 2e5],
            ["all-reduce-start.2", offset + 3e5, 2e5],
            ["gmres_spmv_banded.4", offset + 4e5, 2e5],
            ["collective-permute-done.7", offset + 7e5, 5e4]]


def test_whole_window_roofline_divides_by_the_chips():
    """Four planes, each running one shard's ops: the global bytes over
    four chips' peak read what one shard's bytes read on one chip."""
    four = _run_on({f"/device:TPU:{p}": _shard_ops(p * 1e3)
                    for p in range(4)}, chips=4)
    one = _run_on({"/device:TPU:0": _shard_ops(0.0)}, chips=1)
    shard_bytes = 3.5e9
    assert four.trace.window_s == one.trace.window_s
    assert roofline.window_share(four, 4 * shard_bytes) == pytest.approx(
        roofline.window_share(one, shard_bytes), rel=1e-12)
    assert roofline.window_share(one, shard_bytes) == roofline.share(
        one, shard_bytes, one.trace.window_s)


def test_exposed_collectives_count_only_uncovered_time():
    planes = {f"/device:TPU:{p}": _shard_ops(p * 1e3) for p in range(4)}
    s = _run_on(planes, chips=4).trace
    for p in planes:
        # all-reduce 2e5 ns, 1e5 of it under the second mat-vec; the halo
        # exchange 5e4 ns under nothing but the loop that encloses it.
        assert s.collective_by_plane()[p] == pytest.approx(2.5e-4)
        assert s.exposed_collective_by_plane()[p] == pytest.approx(1.5e-4)
        assert s.busy_by_plane()[p] == pytest.approx(9e-4)
    assert trace.is_collective("%all-gather.1 = f32[8] all-gather(x)")
    assert not trace.is_collective("%fusion.2 = f32[8] fusion(x)")


def test_busy_by_plane_names_the_busiest_and_the_idlest_chip():
    planes = {"/device:TPU:0": [["a", 0.0, 4e5]],
              "/device:TPU:1": [["a", 0.0, 1e5], ["b", 5e5, 1e5]]}
    s = _run_on(planes, chips=2).trace
    assert s.busy_by_plane() == pytest.approx(
        {"/device:TPU:0": 4e-4, "/device:TPU:1": 2e-4})
    assert s.busy_s == pytest.approx(3e-4)
    assert s.collective_by_plane() == {p: 0.0 for p in planes}


# The recorded one-chip trace's readings as the parent's reduction gave
# them, before collectives and planes were read.
RECORDED = {
    "busy_s": 0.014488813000000001,
    "kernel_s": 0.008375325000000001,
    "breakdown": {
        "device_ops": [
            ["gmres_arnoldi_fused", 0.005000912000000001],
            ["pad", 0.0048850130000000005],
            ["gmres_matvec", 0.003374413],
            ["select_dynamic-update-slice_fusion", 0.000164153],
            ["dynamic_slice", 0.000155276],
            ["fusion", 8.788900000000001e-05],
            ["slice_reduce_fusion", 2.3482e-05],
            ["broadcast_in_dim", 1.2891e-05],
            ["and_reduce_fusion", 8.570000000000001e-06],
            ["dynamic-update-slice", 8.020000000000001e-06]],
        "idle_gaps": [["bench.solve", 0.0029756970000000002]]},
}


def test_one_plane_readings_are_unchanged():
    raw = json.loads((ROOT / "bench/testdata/trace_dense_solve.json")
                     .read_text())
    s = trace.Summary(raw)
    assert s.busy_s == RECORDED["busy_s"]
    assert s.kernel_s(["gmres_arnoldi_fused", "gmres_matvec"]) == \
        RECORDED["kernel_s"]
    assert s.breakdown() == RECORDED["breakdown"]
    (plane,) = s.ops
    assert s.busy_by_plane() == {plane: pytest.approx(s.busy_s, rel=1e-12)}
    assert s.collective_by_plane() == {plane: 0.0}
    assert s.exposed_collective_by_plane() == {plane: 0.0}


if __name__ == "__main__":
    print(json.dumps(_runs(pathlib.Path(sys.argv[1]))), flush=True)
