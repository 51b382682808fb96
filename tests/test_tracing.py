"""What a profiler trace of the program shows: the server's host spans and
their metadata, the per-request queue and lane seconds, the named scopes
in the compiled solver, and the handle's trace counter.  CPU only."""
import ast
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import operators, stencils
from repro.core.gmres import gmres
from repro.core.preconditioners import make_preconditioner
from repro.runtime import faultinject
from repro.serve import SolverServer
from repro.serve.request import DONE, REJECTED

PHASES = ["repro.serve.admit", "repro.serve.pack", "repro.serve.dispatch",
          "repro.serve.sync", "repro.serve.retire"]


@pytest.fixture(autouse=True)
def _no_fault_schedule(monkeypatch):
    monkeypatch.delenv("REPRO_FAULT", raising=False)
    faultinject.reset()
    yield
    faultinject.reset()


def _server(n=16, k=2, m=16, **kw):
    op = operators.DenseOperator(
        operators.random_diagdom(jax.random.PRNGKey(0), n))
    return SolverServer(op, m=m, k=k, **kw)


def _rhs(n, seed):
    return np.random.default_rng(seed).standard_normal(n)


def _program_spans(log_dir):
    """``(name, start_ns, end_ns, metadata)`` of the trace's ``repro.``
    host spans, by start time."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                              {k: v for k, v in e.stats})
                             for e in line.events
                             if e.name.startswith("repro."))
    return sorted(spans, key=lambda s: s[1])


def test_step_spans_nest_its_phases_in_order(tmp_path):
    # m = n: one cycle solves exactly, so each request retires in the tick
    # that placed it and the third takes the second tick.
    srv = _server()
    srv.submit(_rhs(16, 9), tol=1e-6)
    srv.run()                                    # compile outside the trace
    rids = [srv.submit(_rhs(16, i), tol=1e-6) for i in range(3)]
    jax.profiler.start_trace(str(tmp_path))
    retired = [srv.step(), srv.step()]
    jax.profiler.stop_trace()

    spans = _program_spans(str(tmp_path))
    steps = [s for s in spans if s[0] == "repro.serve.step"]
    assert len(steps) == 2
    placed = [[0, 1], [2]]
    for (_, a, b, _), want_placed, got in zip(steps, placed, retired):
        inside = [s for s in spans
                  if a <= s[1] and s[2] <= b and s[0] != "repro.serve.step"]
        assert [s[0] for s in inside] == PHASES
        for (_, s1, e1, _), (_, s2, _, _) in zip(inside, inside[1:]):
            assert e1 <= s2                      # in order, not overlapping
        meta = {s[0]: s[3] for s in inside}
        assert ast.literal_eval(meta["repro.serve.pack"]["rids"]) \
            == [rids[i] for i in want_placed]
        assert ast.literal_eval(meta["repro.serve.retire"]["rids"]) \
            == [r.req.rid for r in got] == [rids[i] for i in want_placed]
    assert all(srv.results[r].status == DONE for r in rids)


def test_submit_span_carries_the_rid(tmp_path):
    srv = _server()
    jax.profiler.start_trace(str(tmp_path))
    rid = srv.submit(_rhs(16, 0), tol=1e-6)
    jax.profiler.stop_trace()
    subs = [s for s in _program_spans(str(tmp_path))
            if s[0] == "repro.serve.submit"]
    assert len(subs) == 1 and int(subs[0][3]["rid"]) == rid


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_queue_and_lane_seconds_follow_the_clock():
    clk = _Clock()
    # One lane and m = 2: each request takes several ticks in the lane,
    # and the second waits in the queue for the first.
    srv = _server(k=1, m=2, clock=clk)
    clk.t = 0.5
    r0 = srv.submit(_rhs(16, 0), tol=1e-6, max_restarts=40)
    clk.t = 0.75
    r1 = srv.submit(_rhs(16, 1), tol=1e-6, max_restarts=40)
    retired_at = {}
    for tick in range(1, 60):
        clk.t = float(tick)
        for r in srv.step():
            retired_at[r.req.rid] = clk.t
        if len(retired_at) == 2:
            break
    a, b = srv.results[r0], srv.results[r1]
    assert a.status == DONE and b.status == DONE
    assert retired_at[r0] > 2.0                  # several ticks in the lane
    assert a.queue_s == pytest.approx(1.0 - 0.5)
    assert a.lane_s == pytest.approx(retired_at[r0] - 1.0)
    # Placed in the tick after the first one retired.
    assert b.queue_s == pytest.approx(retired_at[r0] + 1.0 - 0.75)
    for rid, t_submit in ((r0, 0.5), (r1, 0.75)):
        out = srv.results[rid]
        assert out.queue_s + out.lane_s == pytest.approx(
            retired_at[rid] - t_submit)
    bad = srv.submit(np.full(16, np.nan))
    assert srv.results[bad].status == REJECTED
    assert srv.results[bad].queue_s is None and srv.results[bad].lane_s is None
    assert not srv._submitted and not srv._placed   # nothing left behind


def test_cycle_is_traced_once_over_many_ticks():
    srv = _server(k=2, m=4)
    for i in range(8):
        srv.submit(_rhs(16, i), tol=1e-6, max_restarts=40)
    ticks = srv.run()
    assert ticks > 4
    assert srv.metrics()["cycle_traces"] == 1


def _scopes(hlo_text):
    """The named scopes in a compiled program's ``op_name`` metadata."""
    names = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        names.update(path.split("/"))
    return names


PROGRAM_SCOPES = {"operator", "precond", "orthogonalize", "arnoldi_step",
                  "lsq", "residual"}


@pytest.mark.parametrize("gs", ["cgs2", "cgs2_pipelined"])
@pytest.mark.parametrize("pc", [None, "banded_ilu0"])
def test_gmres_program_names_its_parts(gs, pc):
    op = stencils.convection_diffusion_2d(8)
    precond = None if pc is None else make_preconditioner(pc, op)
    b = jnp.ones((op.shape[0],), jnp.float32)

    def solve(b):
        return gmres(op, b, m=8, tol=1e-5, max_restarts=3, gs=gs,
                           precond=precond)

    text = jax.jit(solve).lower(b).compile().as_text()
    assert "residual/operator" in text
    want = {"operator", "orthogonalize", "lsq", "residual"}
    if pc is not None:
        want.add("precond")
    assert _scopes(text) & PROGRAM_SCOPES == want


def test_fused_step_and_server_cycle_name_their_parts():
    a = operators.random_diagdom(jax.random.PRNGKey(0), 40)
    op = operators.DenseOperator(a, "pallas")
    b = jnp.ones((40,), jnp.float32)
    text = jax.jit(lambda b: gmres(op, b, m=8, gs="fused")).lower(
        b).compile().as_text()
    # The basis writes stay with the orthogonalization.
    assert _scopes(text) & PROGRAM_SCOPES == {"operator", "arnoldi_step",
                                              "orthogonalize", "lsq",
                                              "residual"}
    srv = SolverServer(op, m=8, k=2)
    text = srv.handle.lower().compile().as_text()
    assert _scopes(text) & PROGRAM_SCOPES == {"operator", "orthogonalize",
                                              "lsq", "residual"}
