"""The chip benchmark's harness on the CPU, at sizes a test run holds.

Covers the traffic generator, the window rules of the end-to-end metrics
on a fake clock, the time stepper's return to its first state, the trace
reduction against a recorded trace, the refusal of a CPU device and of an
unknown device kind, the names and units of ``BENCHMARK.json``, a cell
defined only here, the check reading one answer at a time, the check
refusing each control, and the check refusing runs whose timed path is
broken.
"""
from __future__ import annotations

import collections
import json
import math
import pathlib
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import generator, harness, roofline, trace  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# --------------------------------------------------------------------------
# A root with tiny cells, defined only here
# --------------------------------------------------------------------------
def _tiny_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A checkout-like root whose BENCHMARK.json adds cells, a traffic mix
    and a metric of its own, all as new files."""
    cfgs = tmp_path / "bench" / "configs"
    cfgs.mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    dense = json.loads((ROOT / "bench/configs/paper_dense_n10k.json")
                       .read_text())
    dense.update(n=256)
    conv = json.loads((ROOT / "bench/configs/convdiff2d_1024_be.json")
                      .read_text())
    # On a 16 x 16 grid tau = 4 shrinks the stepper's state by a factor
    # 0.63 a step, to 1e-18 within the some hundred steps of a test run,
    # where float32 norms underflow; tau = 0.1 shrinks it by about 1 % a
    # step.
    conv.update(nx=16, ny=16, tau=0.1)
    (cfgs / "tiny_dense.json").write_text(json.dumps(dense))
    (cfgs / "tiny_conv.json").write_text(json.dumps(conv))
    (tmp_path / "bench/traffic/tiny_serve.json").write_text(json.dumps({
        "loop": "open", "rate": 200.0, "tol_loguniform": [1e-6, 1e-4],
        "pool": 8, "check_sample": 16}))
    # Ten times the rate: more requests in flight than the server has
    # lanes, so every lane serves some, whatever the host's speed.
    (tmp_path / "bench/traffic/tiny_burst.json").write_text(json.dumps({
        "loop": "open", "rate": 2000.0, "tol_loguniform": [1e-6, 1e-4],
        "pool": 8, "check_sample": 16}))
    (tmp_path / "bench/metrics/solves_in_window.py").write_text(
        "def read(run):\n    return float(len(run.solves)) or None\n")
    spec = {
        "configs": [
            {"name": "tiny_dense", "file": "bench/configs/tiny_dense.json"},
            {"name": "tiny_conv", "file": "bench/configs/tiny_conv.json"}],
        "workloads": [
            {"name": "tiny.solve", "config": "tiny_dense",
             "traffic": "solve_loop", "chips": 1},
            {"name": "tiny.ilu0", "config": "tiny_conv",
             "traffic": "timestep_ilu0", "chips": 1},
            {"name": "tiny.serve", "config": "tiny_dense",
             "traffic": "tiny_serve", "chips": 1},
            {"name": "tiny.serve_burst", "config": "tiny_dense",
             "traffic": "tiny_burst", "chips": 1}],
        "end_to_end": [dict(m) for m in SPEC["end_to_end"]],
        "per_layer": [
            {"name": "solves_in_window", "unit": "solves", "better": "higher",
             "source": "program_counter", "layer": "solver",
             "moves": "solve_ms", "workloads": ["tiny.solve"]}],
        "run_seconds": 1,
    }
    cells = {"solve_ms": ["tiny.solve"], "step_ms.ilu0": ["tiny.ilu0"],
             "step_ms.plain": []}
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = cells.get(m["name"],
                                       ["tiny.serve", "tiny.serve_burst"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture
def ref_kernels():
    from repro.kernels import tuning

    with tuning.force_kernel_mode("ref"):
        yield


def _run(root, cell, seed=20240601, seconds=0.3):
    import time

    return harness.run_cell(root, cell, seed, seconds, False,
                            t_process=time.perf_counter(),
                            require_tpu=False)


# --------------------------------------------------------------------------
# Traffic
# --------------------------------------------------------------------------
TRAFFIC = {"loop": "open", "rate": 400.0, "tol_loguniform": [1e-6, 1e-4],
           "pool": 64}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_schedule_repeats_per_seed(seed):
    a = generator.open_schedule(TRAFFIC, seed, 5.0)
    b = generator.open_schedule(TRAFFIC, seed, 5.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert np.all(np.diff(a.due) >= 0)
    assert a.due[0] >= 0 and a.due[-1] < 5.0
    assert abs(len(a.due) - 2000) <= 1
    assert np.all((a.tol >= 1e-6) & (a.tol <= 1e-4))
    np.testing.assert_array_equal(generator.pool_order(TRAFFIC, seed),
                                  generator.pool_order(TRAFFIC, seed))


def test_seeds_reorder_the_same_work():
    a = generator.open_schedule(TRAFFIC, 1, 5.0)
    b = generator.open_schedule(TRAFFIC, 2, 5.0)
    assert not np.array_equal(a.tol, b.tol)
    np.testing.assert_allclose(np.sort(a.tol), np.sort(b.tol))
    # The same gaps in another order: only the one after the last request
    # differs between the two.
    ga, gb = np.sort(np.diff(a.due)), np.sort(np.diff(b.due))
    assert abs(len(ga) - len(gb)) <= 1
    both = np.intersect1d(np.round(ga, 12), np.round(gb, 12))
    assert len(both) >= min(len(ga), len(gb)) - 2
    assert sorted(generator.pool_order(TRAFFIC, 1)) == list(range(64))


@pytest.mark.parametrize("seed", [3, 2**31 + 99])
def test_first_states_are_one_smooth_field_up_to_sign(seed):
    """A time stepper's first state: the configured sine-mode amplitudes,
    the same for one seed, and for every seed the same field up to its
    sign, so that every seed steps through the same work."""
    import jax

    from bench.configs import banded_stencil

    nx, ny, k = 24, 16, 4
    cfg = {"nx": nx, "ny": ny, "initial": {"modes": k, "pattern": 0}}

    def fields(s):
        key = jax.random.PRNGKey(generator.key_int(s, "system"))
        return np.asarray(banded_stencil.smooth_fields(cfg, key, 6),
                          np.float64)

    f1, f2 = fields(seed), fields(seed)
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(np.abs(f1), np.abs(fields(seed + 1)))
    np.testing.assert_array_equal(np.abs(f1), np.abs(f1[:1]).repeat(6, 0))
    p = np.arange(1, k + 1)[:, None]
    sx = np.sin(np.pi * p * np.arange(1, nx + 1) / (nx + 1))
    sy = np.sin(np.pi * p * np.arange(1, ny + 1) / (ny + 1))
    c = 4.0 / ((nx + 1) * (ny + 1)) * np.einsum(
        "cyx,qy,px->cqp", f1.reshape(6, ny, nx), sy, sx)
    amp = 1.0 / (p ** 2 + p.T ** 2)
    np.testing.assert_allclose(np.abs(c), np.broadcast_to(amp, c.shape),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# Window rules on a fake clock
# --------------------------------------------------------------------------
class FakeClock:
    def __init__(self):
        self.t = 100.0

    def now(self):
        return self.t

    def sleep(self, s):
        self.t += max(0.0, s)


Result = collections.namedtuple(
    "Result", "x inner_steps restarts converged")


def _ctx(root, cell, seconds, monkeypatch, clock):
    monkeypatch.setattr(harness, "_now", clock.now)
    monkeypatch.setattr(harness, "_sleep", clock.sleep)
    layout = harness.Layout(root)
    ctx = harness.Context(layout, layout.cell(cell), 5, seconds, False,
                          clock.now(), None)
    ctx.run = harness.Run(cell=ctx.cell, config=ctx.cfg,
                          traffic=ctx.traffic, seed=5, seconds=seconds,
                          shapes={}, peaks=None)
    return ctx


def test_solve_ms_counts_whole_solves_and_stalls(tiny_root, monkeypatch):
    clock = FakeClock()
    times = iter([0.3, 0.3, 0.9, 0.3, 0.3, 0.3])   # the third solve stalls

    def fake_solve(b, x0):
        clock.t += next(times)
        return Result(np.zeros(2), np.int32(5), np.int32(1), np.bool_(True))

    monkeypatch.setattr(harness, "make_solve",
                        lambda *a: (fake_solve, ()))
    ctx = _ctx(tiny_root, "tiny.solve", 1.5, monkeypatch, clock)
    ctx.pool = [np.ones(2)] * int(ctx.traffic["pool"])
    harness.closed_loop(ctx)
    # warm-up 0.3, then 0.3 + 0.9 + 0.3 = 1.5 s: the window closes on the
    # solve that reaches 1.5 s, and every solve in it counts.
    assert len(ctx.run.solves) == 3
    assert ctx.run.window_s == pytest.approx(1.5)
    reader = harness.Layout(tiny_root).reader("solve_ms")
    assert reader.read(ctx.run) == pytest.approx(500.0)


def test_time_stepper_chains_its_answers(tiny_root, monkeypatch):
    """Each step's right-hand side and first guess are the answer of the
    step before; the first step starts from the pool's first state."""
    clock = FakeClock()
    seen = []

    def fake_solve(b, x0):
        clock.t += 0.25
        seen.append((np.asarray(b), x0))
        return Result(np.asarray(b) + 1.0, np.int32(3), np.int32(1),
                      np.bool_(True))

    monkeypatch.setattr(harness, "make_solve", lambda *a: (fake_solve, ()))
    ctx = _ctx(tiny_root, "tiny.ilu0", 1.0, monkeypatch, clock)
    ctx.pool = [np.full(2, 10.0)]
    harness.closed_loop(ctx)
    assert len(seen) == 1 + 4            # the warm-up step, then 4 timed
    for i, (b, x0) in enumerate(seen):
        np.testing.assert_array_equal(b, np.full(2, 10.0 + i))
        np.testing.assert_array_equal(x0, b)
    reader = harness.Layout(tiny_root).reader("step_ms.ilu0")
    assert reader.read(ctx.run) == pytest.approx(250.0)


def test_time_stepper_starts_again_every_1024_steps(tiny_root,
                                                    monkeypatch):
    """Step 1024 (the warm-up is step 0) takes the first state, with its
    seeded sign, as its right-hand side and first guess, before the field
    can underflow; the steps on either side chain."""
    clock = FakeClock()
    seen = []

    def fake_solve(b, x0):
        clock.t += 1e-3
        seen.append((np.asarray(b), np.asarray(x0)))
        return Result(np.asarray(b) + 1.0, np.int32(3), np.int32(1),
                      np.bool_(True))

    monkeypatch.setattr(harness, "make_solve", lambda *a: (fake_solve, ()))
    ctx = _ctx(tiny_root, "tiny.ilu0", 1.03, monkeypatch, clock)
    first = np.full(2, 10.0)
    ctx.pool = [first]
    harness.closed_loop(ctx)
    assert harness.CHAIN_STEPS == 1024 and len(seen) > 1026
    np.testing.assert_array_equal(seen[1024][0], first)
    for step, (b, x0) in enumerate(seen):
        np.testing.assert_array_equal(x0, b)
        np.testing.assert_array_equal(b, first + step % 1024)


def test_latency_runs_from_the_due_time(tiny_root):
    """Requests are timed from their due time; one that failed or never
    came back counts at what it had waited when the run ended."""
    run = harness.Run(cell={}, config={}, traffic={}, seed=0, seconds=1.0,
                      shapes={}, peaks=None, window_s=1.0)
    run.requests = [{"due": 0.01 * i, "done": 0.01 * i + 0.02,
                     "status": "done", "wait": 0.02, "late": 5.0}
                    for i in range(45)]
    run.requests += [{"due": 0.9, "done": 1.24, "status": "done",
                      "wait": 0.34, "late": 5.0}] * 10
    run.requests += [{"due": 0.97, "done": None, "status": "unfinished",
                      "wait": math.nan, "late": 60.5}] * 45
    layout = harness.Layout(tiny_root)
    p50 = layout.reader("serve_latency_ms_p50")
    rate = layout.reader("serve_solves_per_s")
    assert p50.read(run) == pytest.approx(340.0)
    assert rate.read(run) == pytest.approx(45.0)
    for k in range(10):
        run.requests[k] = dict(run.requests[k], status="failed")
    assert p50.read(run) == pytest.approx(5000.0)
    assert rate.read(run) == pytest.approx(35.0)


class FakeServer:
    """Serves every waiting request in each tick of ``tick_s`` seconds."""

    def __init__(self, clock, tick_s):
        self.clock, self.tick_s = clock, tick_s
        self.results, self.waiting, self.rid = {}, [], 0
        self.state = self
        self.ingress = self
        self.handle = self

    busy = property(lambda self: bool(self.waiting))

    def peek(self):
        return None

    def submit(self, b, tol, max_restarts):
        self.rid += 1
        self.waiting.append(self.rid)
        return self.rid

    def step(self):
        from repro.serve.request import DONE

        Req = collections.namedtuple("Req", "rid")
        Ret = collections.namedtuple("Ret", "req status")
        self.clock.t += self.tick_s
        out = [Ret(Req(r), DONE) for r in self.waiting]
        for r in self.waiting:
            self.results[r] = collections.namedtuple("O", "x status")(
                np.zeros(2), DONE)
        self.waiting = []
        return out


def test_open_loop_times_from_due_and_drains(tiny_root, monkeypatch):
    clock = FakeClock()
    ctx = _ctx(tiny_root, "tiny.serve", 1.0, monkeypatch, clock)
    ctx.pool = [np.ones(2)] * 8
    server = FakeServer(clock, 0.25)
    ctx.family = type("F", (), {
        "program_operator": staticmethod(lambda cfg, arrays: None)})
    import repro.serve as serve

    monkeypatch.setattr(serve, "SolverServer", lambda op, **kw: server)
    server.run = server.waiting.clear
    server.k = 8
    server.cycle = lambda *a, **k: (None, None, np.zeros(8))
    harness.open_loop(ctx)
    reqs = ctx.run.requests
    assert len(reqs) == 200 and all(r["status"] == "done" for r in reqs)
    waits = np.array([r["wait"] for r in reqs])
    assert waits.min() >= 0 and waits.max() <= 0.5 + 1e-9
    assert max(r["done"] for r in reqs) > 1.0      # drained past the close
    assert ctx.run.window_s == 1.0


# --------------------------------------------------------------------------
# Trace reduction
# --------------------------------------------------------------------------
def test_interval_arithmetic():
    evs = [("a.1", 0, 10), ("b.2", 5, 10), ("a.3", 30, 5), ("c", 50, 0)]
    assert trace.intervals(evs) == [(0, 15), (30, 35), (50, 50)]
    assert trace.busy_ns(evs) == 20
    assert trace.kernel_ns(evs, ["a"]) == 15
    assert trace.clip(evs, 8, 32) == [("a.1", 8, 2), ("b.2", 8, 7),
                                      ("a.3", 30, 2)]
    host = [("bench.window", -5, 100), ("bench.wait", 16, 10)]
    gaps = dict(trace.idle_gaps(evs, host, 0, 60))
    assert gaps == {"bench.wait": pytest.approx(15e-9),
                    "host_other": pytest.approx(25e-9)}


def _brute_busy(events, start, end, step=1000.0):
    """Busy time by sampling, the slow way, for the recorded trace."""
    t = np.arange(start, end, step)
    on = np.zeros_like(t, bool)
    for _, s, d in events:
        on |= (t >= s) & (t < s + d)
    return on.sum() * step


def test_reduction_matches_recorded_trace():
    raw = json.loads((ROOT / "bench/testdata/trace_dense_solve.json")
                     .read_text())
    s = trace.Summary(raw)
    evs = s.all_ops()
    assert s.start < s.end and evs
    busy = _brute_busy(evs, s.start, s.end)
    assert s.busy_s == pytest.approx(busy * 1e-9, rel=2e-2)
    names = {trace.base_name(n) for n, _, _ in evs}
    assert {"gmres_arnoldi_fused", "gmres_matvec"} <= names
    total = sum(d for _, _, d in evs)
    assert s.kernel_s(names) == pytest.approx(total * 1e-9)
    kernels = s.kernel_s(["gmres_arnoldi_fused", "gmres_matvec"])
    assert 0 < kernels <= s.busy_s * 1.0001
    b = s.breakdown()
    assert b["device_ops"][0][0] in names and len(b["device_ops"]) <= 10
    idle = sum(t for _, t in b["idle_gaps"])
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)


# --------------------------------------------------------------------------
# Devices
# --------------------------------------------------------------------------
def test_unknown_device_kind_is_refused():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v9 imaginary")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_cpu_device_is_refused():
    with pytest.raises(harness.NoChip):
        harness.device_info(1, require_tpu=True)


def _bench_cli(cwd, env_extra=None):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("REPRO_KERNELS", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dense10k.solve",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_cli_on_cpu_exits_without_result():
    out = _bench_cli(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_cli_without_the_program_exits_without_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench_cli(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0 and out.stdout.strip() == ""


# --------------------------------------------------------------------------
# BENCHMARK.json
# --------------------------------------------------------------------------
def test_benchmark_names_and_units():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [x["name"] for x in SPEC["configs"] + SPEC["workloads"]
             + metrics]
    assert len(set(x["name"] for x in metrics)) == len(metrics)
    for name in names:
        assert NAME.match(name), name
    for c in SPEC["workloads"]:
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        assert 1 <= len(c["why"]) <= 200 and c["chips"] in (1, 4)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    layout = harness.Layout(ROOT)
    for c in SPEC["workloads"]:
        reported = [m["name"] for m in layout.metrics(c, False)]
        assert "setup_s" in reported and len(reported) >= 2
        assert layout.metrics(c, True)
        layout.traffic(c)
        layout.family(layout.config(c))
        for m in layout.metrics(c, False) + layout.metrics(c, True):
            assert callable(layout.reader(m["name"]).read)
    assert sum(c["chips"] == 4 for c in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)


# --------------------------------------------------------------------------
# Runs on the CPU: a cell defined here, the controls, the faults
# --------------------------------------------------------------------------
def test_cell_defined_only_in_files(tiny_root, ref_kernels):
    layout = harness.Layout(tiny_root)
    cell = layout.cell("tiny.solve")
    assert [m["name"] for m in layout.metrics(cell, True)] == [
        "solves_in_window"]
    res = _run(tiny_root, "tiny.solve")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"solve_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", ["tiny.solve", "tiny.ilu0"])
def test_control_is_refused_and_program_is_not(tiny_root, ref_kernels, cell):
    from bench import control

    ctl = control.readings(tiny_root, cell, 11, 4, require_tpu=False)
    assert ctl["refused"], ctl
    res = _run(tiny_root, cell, seed=11)
    check = res["checks"]["resid_over_tol_max"]
    assert res["correct"] and check["value"] <= check["limit"]


@pytest.mark.parametrize("cell", ["tiny.solve", "tiny.ilu0"])
def test_check_one_answer_at_a_time_reads_as_the_stacked_check(tiny_root,
                                                               cell):
    """The check hands the family its answers as they lie on the device,
    copied to the host one at a time, and reads bit for bit what the
    family reads from all of them stacked."""
    import jax
    import jax.numpy as jnp

    layout = harness.Layout(tiny_root)
    cfg = layout.config(layout.cell(cell))
    family = layout.family(cfg)
    arrays, pool = family.build(cfg, jax.random.PRNGKey(3), 4)
    host = family.HostSystem(cfg, arrays)
    rng = np.random.default_rng(5)
    sample = [(jnp.asarray(pool[i % 4] + 1e-5 * rng.standard_normal(
        pool.shape[1]), jnp.float32), pool[i % 4], 1e-5 * (1 + i % 3))
        for i in range(12)]
    xs = np.stack([np.asarray(x, np.float32) for x, _, _ in sample])
    bs = np.stack([np.asarray(b, np.float32) for _, b, _ in sample])
    tols = np.asarray([t for _, _, t in sample])
    stacked = float(np.max(host.residuals(xs, bs) / tols))
    got = harness.check_answers(host, sample, cfg)["resid_over_tol_max"]
    assert got["value"] == stacked and got["value"] > 0


def _break_solve(monkeypatch, how):
    import jax.numpy as jnp

    import repro.core as core

    solve = core.gmres

    def broken(*a, **k):
        res = solve(*a, **k)
        x = (res.x * 1.001 if how == "answer_altered"
             else jnp.zeros_like(res.x))
        return res._replace(x=x)

    monkeypatch.setattr(core, "gmres", broken)


@pytest.mark.parametrize("how", ["answer_altered", "state_unchanged"])
def test_broken_solve_is_not_correct(tiny_root, ref_kernels, monkeypatch,
                                     how):
    _break_solve(monkeypatch, how)
    res = _run(tiny_root, "tiny.solve")
    assert not res["correct"]
    assert res["checks"]["resid_over_tol_max"]["value"] > \
        res["checks"]["resid_over_tol_max"]["limit"]


@pytest.mark.parametrize("how", ["half_batch_left_out", "answer_altered"])
def test_broken_server_is_not_correct(tiny_root, ref_kernels, monkeypatch,
                                      how):
    import jax.numpy as jnp

    from repro.serve import handles

    cycle = handles.gmres_batched_cycle

    def broken(a, b, x, *, active=None, **kw):
        k = b.shape[0]
        if how == "half_batch_left_out":
            return cycle(a, b, x, active=active & (jnp.arange(k) < k // 2),
                         **kw)
        x2, beta, inner = cycle(a, b, x, active=active, **kw)
        return x2 * 1.001, beta, inner

    monkeypatch.setattr(handles, "gmres_batched_cycle", broken)
    # The server fills its lowest idle lane first: at the tiny cell's rate
    # a fast host keeps four or fewer requests in flight and never uses
    # the lanes left out, so the burst, which fills every lane, is run.
    res = _run(tiny_root, "tiny.serve_burst")
    assert not res["correct"]


def test_server_cell_is_correct(tiny_root, ref_kernels):
    res = _run(tiny_root, "tiny.serve")
    assert res["correct"] and res["attempted"] >= 50
    assert set(res["metrics"]) == {"serve_latency_ms_p50",
                                   "serve_solves_per_s", "setup_s"}


def test_server_burst_cell_is_correct(tiny_root, ref_kernels):
    """The burst the broken server is refused on reads correct with the
    server as it is."""
    res = _run(tiny_root, "tiny.serve_burst")
    assert res["correct"] and res["attempted"] >= 500
