"""The reduction of the program's spans and named scopes (``bench/scopes``)
on synthetic events and on a recorded excerpt of a traced serve cell, and
the program objects ``bench/phases.py`` keeps, on tiny CPU cells."""
from __future__ import annotations

import json
import pathlib
import re
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, phases, roofline, scopes, trace  # noqa: E402

HLO = """HloModule jit_solve, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %divide.2 = f32[8]{0} divide(%param_0, %param_0), metadata={op_name="jit(solve)/while/body/orthogonalize/div" stack_frame_id=3}
}

ENTRY %main.9 (a.1: f32[8,8]) -> f32[8] {
  %a.1 = f32[8,8]{1,0} parameter(0)
  %pad.3 = f32[16,8]{1,0} pad(%a.1, %constant.4), padding=0_8x0_0, metadata={op_name="jit(solve)/residual/operator/jit(matvec)/jit(_pad)/pad" stack_frame_id=6}
  %gmres_matvec.1 = f32[16,1]{1,0} custom-call(%pad.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(solve)/operator/jit(matvec)/pallas_call"}
  %divide_fusion = f32[8]{0} fusion(%gmres_matvec.1), kind=kLoop, calls=%fused_computation.1
  %copy.7 = f32[8]{0} copy(%divide_fusion)
  ROOT %while.1 = f32[8]{0} while(%copy.7), condition=%cond, body=%body
}
"""


def test_op_names_from_compiled_text():
    names = scopes.op_names(HLO)
    assert scopes.module_of(HLO) == "jit_solve"
    assert scopes.scope_of(names["pad.3"]) == "residual/operator"
    assert scopes.scope_of(names["gmres_matvec.1"]) == "operator"
    # A fusion without metadata of its own takes its computation's.
    assert scopes.scope_of(names["divide_fusion"]) == "orthogonalize"
    # A copy XLA inserted takes its operand's.
    assert scopes.scope_of(names["copy.7"]) == "orthogonalize"
    assert "a.1" not in names and "while.1" in names
    assert scopes.module_name("jit_solve(1700993271882)") == "jit_solve"
    assert scopes.instruction("%pad.3 = f32[16,8]{1,0} pad(f32[8,8]") \
        == "pad.3"
    assert scopes.scope_of("jit(f)/while/body/add") == ""
    assert scopes.scope_of("jit(f)/lsq/jit(g)/lsq/precond/add") \
        == "lsq/precond"


def test_scope_time_sums_by_module_and_instruction():
    plane = "/device:TPU:0"
    ops = {plane: [("%pad.3 = f32[16,8] pad(...)", 10, 5),
                   ("%gmres_matvec.1 = f32[16,1] custom-call(...)", 20, 7),
                   ("%while.1 = f32[8] while(...)", 30, 100),
                   ("%divide_fusion = f32[8] fusion(...)", 40, 3),
                   ("%copy.7 = f32[8] copy(...)", 50, 2),
                   ("%pad.3 = f32[16,8] pad(...)", 300, 4)]}  # other module
    modules = {plane: [("jit_solve(17)", 0, 200), ("jit_other(3)", 290, 20)]}
    scoped = scopes.scoped_ops(ops, modules, {"jit_solve":
                                              scopes.op_names(HLO)})
    # The while loop encloses the ops it runs and is not counted.
    assert [p for p, _, _ in scoped] == ["residual/operator", "operator",
                                         "orthogonalize", "orthogonalize",
                                         ""]
    assert scopes.scope_s(scoped, "operator") == pytest.approx(12e-9)
    assert scopes.scope_s(scoped, "operator", ("residual",)) \
        == pytest.approx(7e-9)
    assert scopes.scope_s(scoped, "orthogonalize") == pytest.approx(5e-9)
    assert scopes.by_scope(scoped)[0] == ["operator", pytest.approx(7e-9)]
    assert scopes.scoped_share(scoped) == pytest.approx(17 / 21)


def _tick(t0, sync=(40, 30), pad=5):
    """A ``bench.tick`` around a step with its five phases, from ``t0``."""
    s0, sd = sync
    step = [("repro.serve.step", t0 + pad, 100),
            ("repro.serve.admit", t0 + pad + 1, 4),
            ("repro.serve.pack", t0 + pad + 6, 10),
            ("repro.serve.dispatch", t0 + pad + 20, 15),
            ("repro.serve.sync", t0 + pad + s0, sd),
            ("repro.serve.retire", t0 + pad + s0 + sd + 1, 20)]
    return [("bench.tick", t0, 100 + 2 * pad)] + step


def test_tick_readings_from_program_spans():
    host = _tick(0) + _tick(1000, sync=(40, 50)) + [
        ("repro.serve.step", 3000, 10),           # a tick with no cycle
        ("repro.serve.admit", 3001, 2),
        ("bench.submit", 2000, 30), ("repro.serve.submit", 2001, 20)]
    assert len(scopes.cycle_ticks(host)) == 2
    assert scopes.tick_sync_ms(host) == pytest.approx(40e-6)
    assert scopes.tick_host_ms(host) == pytest.approx(60e-6)
    assert scopes.tick_span_ms(host) == pytest.approx(110e-6)
    ph = scopes.tick_phases_ms(host)
    assert ph["repro.serve.sync"] == pytest.approx(40e-6)
    assert ph["repro.serve.pack"] == pytest.approx(10e-6)
    assert sum(ph.values()) == pytest.approx(100e-6)
    assert scopes.tick_host_ms([("bench.tick", 0, 5)]) is None
    assert scopes.nearest_rank([3.0, 1.0, 2.0, 4.0], 0.5) == 2.0
    assert scopes.nearest_rank([], 0.5) is None


def test_idle_under_sync_is_named_by_the_innermost_span():
    host = [("bench.window", 0, 1000)] + _tick(0)
    # The device runs before and after the sync's wait, idle in between.
    evs = [("%gmres_matvec.1 = f32[]", 20, 30), ("%copy.2 = f32[]", 85, 20)]
    gaps = dict(trace.idle_gaps(evs, host, 0, 110))
    assert gaps["repro.serve.sync"] == pytest.approx(35e-9)
    bench_only = [e for e in host if not e[0].startswith("repro.")]
    assert dict(trace.idle_gaps(evs, bench_only, 0, 110)) == {
        "bench.tick": pytest.approx(60e-9)}


# --------------------------------------------------------------------------
# The recorded excerpt: two traced cycle ticks of dense10k.serve
# --------------------------------------------------------------------------
EXCERPT = ROOT / "bench/testdata/trace_serve_tick.json"


@pytest.fixture(scope="module")
def excerpt():
    return json.loads(EXCERPT.read_text())


def _serve_run(excerpt, host):
    cell = {"name": "dense10k.serve"}
    run = harness.Run(cell=cell, config={}, traffic={}, seed=0, seconds=1.0,
                      shapes={"n": excerpt["n"]},
                      peaks=roofline.peaks("TPU v5 lite"))
    run.requests = [{"status": "done", "wait": 0.1, "done": 0.5,
                     "due": 0.0, "late": 0.1}]
    run.traced_cycles = excerpt["traced_cycles"]
    run.trace = trace.Summary({"devices": excerpt["devices"], "host": host})
    return run


def test_existing_readers_ignore_the_program_spans(excerpt):
    host = [tuple(e) for e in excerpt["host"]]
    bench_only = [e for e in host if not e[0].startswith("repro.")]
    assert len(bench_only) < len(host)
    layout = harness.Layout(ROOT)
    for metric in ("idle_share.serve", "serve_cycle_roofline"):
        read = layout.reader(metric).read
        with_spans = read(_serve_run(excerpt, host))
        assert with_spans is not None
        assert with_spans == read(_serve_run(excerpt, bench_only))
    a = _serve_run(excerpt, host).trace
    b = _serve_run(excerpt, bench_only).trace
    assert (a.busy_s, a.window_s, a.breakdown()["device_ops"]) \
        == (b.busy_s, b.window_s, b.breakdown()["device_ops"])


def test_excerpt_splits_the_tick_and_names_the_scopes(excerpt):
    host = [tuple(e) for e in excerpt["host"]]
    s = trace.Summary({"devices": excerpt["devices"], "host": host})
    # Host time: step minus sync plus sync is the benchmark's tick, less
    # the harness's own bookkeeping around ``step``.
    total = scopes.tick_host_ms(host) + scopes.tick_sync_ms(host)
    assert total == pytest.approx(scopes.tick_span_ms(host), rel=0.05)
    gaps = dict(trace.idle_gaps(s.all_ops(), host, s.start, s.end))
    idle = sum(gaps.values())
    assert gaps.get("bench.tick", 0.0) <= 0.1 * idle
    assert any(k.startswith("repro.serve.") for k in gaps)
    scoped = scopes.scoped_ops(s.ops, excerpt["modules"],
                               excerpt["op_names"])
    assert scopes.scoped_share(scoped) >= 0.9
    # The pads of A: a pad whose result holds at least n x n entries.
    n = excerpt["n"]

    def pads_a(evs):
        out = []
        for e in evs:
            shape = re.search(r"= f32\[(\d+),(\d+)\]", e[0])
            if (trace.base_name(e[0]) == "pad" and shape
                    and min(map(int, shape.groups())) >= n):
                out.append(e)
        return out

    pads = scopes.scoped_ops({p: pads_a(evs) for p, evs in s.ops.items()},
                             excerpt["modules"], excerpt["op_names"])
    assert pads and all("operator" in p.split("/") for p, _, _ in pads)


# --------------------------------------------------------------------------
# phases.py on tiny CPU cells
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_phases")
    (root / "bench/configs").mkdir(parents=True)
    (root / "bench/traffic").mkdir()
    dense = json.loads((ROOT / "bench/configs/paper_dense_n10k.json")
                       .read_text())
    dense.update(n=128)
    (root / "bench/configs/tiny_dense.json").write_text(json.dumps(dense))
    (root / "bench/traffic/tiny_serve.json").write_text(json.dumps({
        "loop": "open", "rate": 100.0, "tol_loguniform": [1e-6, 1e-4],
        "pool": 8, "check_sample": 8}))
    spec = {"configs": [{"name": "tiny_dense",
                         "file": "bench/configs/tiny_dense.json"}],
            "workloads": [
                {"name": "tiny.solve", "config": "tiny_dense",
                 "traffic": "solve_loop", "chips": 1},
                {"name": "tiny.serve", "config": "tiny_dense",
                 "traffic": "tiny_serve", "chips": 1}],
            "end_to_end": [], "per_layer": [], "run_seconds": 1}
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(autouse=True)
def _short_trace(monkeypatch):
    monkeypatch.setattr(harness, "TRACE_S", 0.3)


def _program_run(root, cell):
    ctx, kept = phases._traced_run(root, cell, 11, 0.4, require_tpu=False)
    try:
        prog = scopes.read_program(ctx.trace_dir)
    finally:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    names = [scopes.op_names(t) for t in phases._compiled_texts(ctx, kept)]
    return ctx, kept, prog, names


def test_phases_keeps_the_closed_loops_program(tiny_root):
    ctx, kept, prog, (names,) = _program_run(tiny_root, "tiny.solve")
    assert ctx.run.solves and any(s["traced"] for s in ctx.run.solves)
    paths = {scopes.scope_of(v) for v in names.values()}
    assert {"residual/operator", "arnoldi_step", "lsq"} <= paths
    assert not [e for e in prog["spans"] if e[0] == scopes.STEP]


def test_phases_keeps_the_servers_program(tiny_root):
    ctx, kept, prog, (names,) = _program_run(tiny_root, "tiny.serve")
    assert kept["server"].metrics()["cycle_traces"] == 1
    assert scopes.cycle_ticks(prog["spans"])
    q = phases._queue_s(ctx, kept)
    assert len(q) == len(ctx.run.requests) > 0
    assert all(x >= 0 for x in q)
    paths = {scopes.scope_of(v) for v in names.values()}
    assert {"operator", "orthogonalize", "residual/operator"} <= paths
