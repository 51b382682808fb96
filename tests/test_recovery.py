"""Self-healing solver stack: detection, ladder, injection, checkpoints.

Layered like the code under test: pure classifier/ladder/breaker units
first (no solves), then the deterministic fault injector, then
``gmres_self_healing`` end-to-end on tiny dense systems — including the
acceptance bar from the issue: a scripted fault at any ladder rung must
converge to the same answer as the fault-free solve within tolerance and
at most one extra restart, and a killed + resumed solve must be
bit-identical to an uninterrupted one.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.gmres import (BREAKDOWN, HEALTHY, NAN_INF, STAGNATED,
                              classify_residuals, gmres)
from repro.core import operators
from repro.core.recovery import (CircuitBreaker, DEGRADATION_SCHEMES,
                                 build_ladder, gmres_self_healing)
from repro.kernels import tuning
from repro.runtime import faultinject
from repro.runtime.faultinject import InjectedFault


@pytest.fixture(autouse=True)
def _isolated_fault_schedule(monkeypatch):
    """Exact-counter tests must not see an ambient REPRO_FAULT (the CI
    injection leg replays OTHER suites under env schedules)."""
    monkeypatch.delenv("REPRO_FAULT", raising=False)
    faultinject.reset()
    yield
    faultinject.reset()


def _op(n=48, seed=0):
    return operators.DenseOperator(
        operators.random_diagdom(jax.random.PRNGKey(seed), n))


def _rhs(n, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(n),
                       jnp.float32)


# =====================================================================
# classify_residuals: the cycle-level health check (pure, jit-safe)
# =====================================================================

def _ring(*vals, window=8):
    h = np.full(window, np.inf)
    h[-len(vals):] = vals
    return jnp.asarray(h)


def test_classify_healthy_decreasing():
    s = classify_residuals(_ring(10.0, 1.0, 0.1), converged=False)
    assert int(s) == HEALTHY


def test_classify_nan_inf():
    assert int(classify_residuals(_ring(1.0, np.nan),
                                  converged=False)) == NAN_INF
    assert int(classify_residuals(_ring(1.0, np.inf),
                                  converged=False)) == NAN_INF


def test_classify_breakdown_growth():
    s = classify_residuals(_ring(1.0, 20.0), converged=False)
    assert int(s) == BREAKDOWN


def test_classify_stagnated_full_window():
    vals = [1.0] * 8                       # flat across the whole ring
    s = classify_residuals(_ring(*vals), converged=False)
    assert int(s) == STAGNATED


def test_classify_partial_window_never_stagnates():
    """Inf left-padding means a young solve (fewer cycles than the
    window) can never be declared stagnated: oldest slot is inf."""
    s = classify_residuals(_ring(5.0, 4.9, 4.8), converged=False)
    assert int(s) == HEALTHY


def test_classify_converged_overrides_plateau():
    """A converged solve sitting at tol for the whole window is DONE,
    not stagnated — and never 'breaks down' from float noise."""
    vals = [1e-7] * 8
    assert int(classify_residuals(_ring(*vals), converged=True)) == HEALTHY


def test_classify_scale_invariant():
    """Thresholds are ratios: scaling the whole history by 1e6 (c·A, c·b)
    must classify identically."""
    for vals, expect in (( [10.0, 1.0, 0.1], HEALTHY),
                         ([1.0, 50.0], BREAKDOWN),
                         ([1.0] * 8, STAGNATED)):
        lo = classify_residuals(_ring(*vals), converged=False)
        hi = classify_residuals(_ring(*[v * 1e6 for v in vals]),
                                converged=False)
        assert int(lo) == int(hi) == expect


def test_classify_priority_nan_beats_breakdown():
    s = classify_residuals(_ring(1.0, np.nan), converged=False)
    assert int(s) == NAN_INF


def test_classify_is_jittable():
    f = jax.jit(lambda h: classify_residuals(h, converged=False))
    assert int(f(_ring(10.0, 1.0))) == HEALTHY


# =====================================================================
# GmresResult.diagnostics: the residual ring on the real solvers
# =====================================================================

def test_gmres_residual_history_chronological():
    op, b = _op(), _rhs(48)
    res = gmres(op, b, m=10, tol=1e-5, max_restarts=30, history=8)
    hist = np.asarray(res.diagnostics.residual_history)
    k = int(res.restarts)
    assert hist.shape == (8,)
    assert int(res.diagnostics.status) == HEALTHY and bool(res.converged)
    # inf padding on the left, then strictly the per-cycle residuals with
    # the FINAL residual in the last slot.
    filled = hist[np.isfinite(hist)]
    assert len(filled) == min(k + 1, 8)    # seed ||b - A x0|| + k cycles
    assert filled[-1] == pytest.approx(float(res.residual), rel=1e-6)
    assert (np.diff(filled) <= 0).all()    # diagdom: monotone decrease
    assert int(res.diagnostics.history_len) == min(k + 1, 8)


def test_gmres_history_window_is_bounded():
    op, b = _op(), _rhs(48)
    res = gmres(op, b, m=4, tol=1e-12, max_restarts=20, history=4)
    assert np.asarray(res.diagnostics.residual_history).shape == (4,)


def test_sstep_carries_diagnostics():
    from repro.core.sstep import gmres_sstep
    op, b = _op(), _rhs(48)
    res = gmres_sstep(op, b, s=2, blocks=5, tol=1e-5, max_restarts=30)
    assert res.diagnostics is not None
    assert int(res.diagnostics.status) == HEALTHY
    assert res.residual_history is not None


def test_nan_system_diagnosed_nan_inf():
    n = 16
    a = jnp.full((n, n), jnp.nan, jnp.float32)
    res = gmres(a, jnp.ones(n, jnp.float32), m=4, tol=1e-5, max_restarts=3)
    assert int(res.diagnostics.status) == NAN_INF
    assert not bool(res.converged)


# =====================================================================
# build_ladder + force_kernel_mode
# =====================================================================

def test_ladder_full_from_top():
    rungs = build_ladder("cgs2_pipelined", mode="compiled")
    assert rungs[0] == ("cgs2_pipelined", "compiled")
    assert rungs[-1] == ("mgs", "ref")
    # 4 schemes at each of 2 modes: a compiled solve steps straight to the
    # reference, never into the Pallas interpreter.
    assert len(rungs) == 8
    assert rungs[4] == ("cgs2_pipelined", "ref")
    assert all(md != "interpret" for _, md in rungs)


def test_ladder_starts_at_callers_scheme():
    rungs = build_ladder("cgs2", mode="ref")
    assert rungs == (("cgs2", "ref"), ("mgs", "ref"))


def test_ladder_unknown_scheme_is_rung_zero():
    rungs = build_ladder("fused", mode="ref")
    assert rungs[0] == ("fused", "ref")
    assert rungs[1:] == tuple((s, "ref") for s in DEGRADATION_SCHEMES)


def test_ladder_rejects_unknown_mode():
    with pytest.raises(ValueError, match="kernel mode"):
        build_ladder("mgs", mode="gpu")


def test_force_kernel_mode_nests_and_restores():
    base = tuning.kernel_mode()
    with tuning.force_kernel_mode("ref"):
        assert tuning.kernel_mode() == "ref"
        with tuning.force_kernel_mode("interpret"):
            assert tuning.kernel_mode() == "interpret"
        assert tuning.kernel_mode() == "ref"
    assert tuning.kernel_mode() == base


def test_force_kernel_mode_rejects_unknown():
    with pytest.raises(ValueError):
        with tuning.force_kernel_mode("tpu"):
            pass


# =====================================================================
# Deterministic fault injector
# =====================================================================

def test_parse_schedule_forms():
    s = faultinject.parse_schedule("core.cycle:3,serve.cycle:*:2,"
                                   "core.cycle_nan:1:*")
    assert s["core.cycle"] == [[3, 1]]
    assert s["serve.cycle"] == [[None, 2]]
    assert s["core.cycle_nan"] == [[1, None]]


def test_parse_schedule_rejects_unknown_site():
    with pytest.raises(ValueError, match="unknown fault site"):
        faultinject.parse_schedule("bogus.site:1")


def test_parse_schedule_rejects_malformed():
    with pytest.raises(ValueError, match="expected"):
        faultinject.parse_schedule("core.cycle")


def test_env_schedule_fires_and_consumes(monkeypatch):
    monkeypatch.setenv("REPRO_FAULT", "core.cycle:2")
    faultinject.reset()
    assert not faultinject.fire("core.cycle", index=1)
    assert faultinject.fire("core.cycle", index=2)
    assert not faultinject.fire("core.cycle", index=2)   # consumed
    assert faultinject.fired["core.cycle"] == 1


def test_context_schedule_wins_over_env(monkeypatch):
    monkeypatch.setenv("REPRO_FAULT", "core.cycle:5")
    faultinject.reset()
    with faultinject.inject("core.cycle", at=5) as entry:
        assert faultinject.fire("core.cycle", index=5)
        assert entry[1] == 0               # the SCOPED entry was consumed
    # The env entry is still live after the context exits.
    assert faultinject.armed("core.cycle")
    assert faultinject.fire("core.cycle", index=5)


def test_armed_is_non_consuming():
    with faultinject.inject("core.cycle", at=1):
        assert faultinject.armed("core.cycle")
        assert faultinject.armed("core.cycle", "serve.cycle")
        assert not faultinject.armed("serve.cycle")
        assert faultinject.fire("core.cycle", index=1)
        assert not faultinject.armed("core.cycle")       # exhausted


def test_check_raises_injected_fault():
    with faultinject.inject("serve.cycle", at=0):
        with pytest.raises(InjectedFault) as ei:
            faultinject.check("serve.cycle", index=0)
    assert ei.value.site == "serve.cycle" and ei.value.index == 0


def test_inject_unknown_site_rejected():
    with pytest.raises(ValueError, match="unknown fault site"):
        with faultinject.inject("nope"):
            pass


def test_reset_rearms_env(monkeypatch):
    monkeypatch.setenv("REPRO_FAULT", "core.cycle:0")
    faultinject.reset()
    assert faultinject.fire("core.cycle", index=0)
    assert not faultinject.fire("core.cycle", index=0)
    faultinject.reset()
    assert faultinject.fire("core.cycle", index=0)       # re-armed


# =====================================================================
# CircuitBreaker (tick-deterministic, no clock)
# =====================================================================

def test_breaker_opens_after_threshold():
    br = CircuitBreaker(threshold=2, cooldown=3, max_trips=2)
    assert br.allow(0)
    br.record_failure(0)
    assert br.state == "closed"
    br.record_failure(1)
    assert br.state == "open" and not br.allow(2)


def test_breaker_half_open_trial_then_close():
    br = CircuitBreaker(threshold=1, cooldown=2, max_trips=3)
    br.record_failure(0)                   # open until 2
    assert not br.allow(1)
    assert br.allow(2) and br.state == "half_open"
    br.record_success()
    assert br.state == "closed" and br.trips == 0


def test_breaker_success_resets_consecutive_count():
    br = CircuitBreaker(threshold=3, cooldown=2, max_trips=1)
    br.record_failure(0), br.record_failure(1)
    br.record_success()
    br.record_failure(2), br.record_failure(3)
    assert br.state == "closed"            # never 3 consecutive


def test_breaker_dies_after_max_trips():
    br = CircuitBreaker(threshold=1, cooldown=1, max_trips=1)
    br.record_failure(0)                   # trip 1 -> open
    br.allow(1)                            # half-open
    br.record_failure(1)                   # trip 2 > max_trips -> dead
    assert br.dead and not br.allow(100)
    br.record_success()                    # death is permanent
    assert br.dead


# =====================================================================
# gmres_self_healing end-to-end
# =====================================================================

def test_fast_path_matches_plain_gmres():
    op, b = _op(), _rhs(48)
    ref = gmres(op, b, m=10, tol=1e-5, max_restarts=40,
                gs="cgs2_pipelined")   # the self-healing default
    res, rep = gmres_self_healing(op, b, m=10, tol=1e-5, max_restarts=40)
    assert rep.fast_path and rep.stepdowns == 0 and rep.faults == 0
    assert bool(res.converged)
    assert int(res.restarts) == int(ref.restarts)
    assert np.array_equal(np.asarray(res.x), np.asarray(ref.x))


def test_stepped_loop_commits_same_cycles_as_fused():
    """The restart-count parity the bench gate leans on: an ARMED (but
    never-firing) schedule forces the stepped loop, which must commit
    exactly the cycles the fused while_loop would."""
    op, b = _op(), _rhs(48)
    ref = gmres(op, b, m=10, tol=1e-5, max_restarts=40)
    with faultinject.inject("core.cycle", at=10_000):    # armed, never hit
        res, rep = gmres_self_healing(op, b, m=10, tol=1e-5,
                                      max_restarts=40)
    assert not rep.fast_path
    assert int(res.restarts) == int(ref.restarts)
    assert bool(res.converged)


@pytest.mark.parametrize("stepdowns", [1, 2])
def test_injected_nan_recovers_via_ladder(stepdowns):
    """A NaN-poisoned cycle is discarded and re-run one rung down; the
    recovered solve must match fault-free within tol and ≤ +1 restart."""
    op, b = _op(), _rhs(48)
    tol = 1e-6                         # m=3: several cycles, so cycle 1 exists
    ref = gmres(op, b, m=3, tol=tol, max_restarts=40, gs="cgs2_pipelined")
    with faultinject.inject("core.cycle_nan", at=1, times=stepdowns):
        res, rep = gmres_self_healing(op, b, m=3, tol=tol,
                                      max_restarts=40)
    assert bool(res.converged)
    assert rep.stepdowns == stepdowns and rep.faults == stepdowns
    assert not rep.gave_up
    assert int(res.restarts) - int(ref.restarts) <= 1
    bnorm = float(jnp.linalg.norm(b))
    assert float(res.residual) <= tol * bnorm
    # Recovered x solves the SAME system: compare through the operator.
    err = np.linalg.norm(np.asarray(res.x) - np.asarray(ref.x))
    assert err / np.linalg.norm(np.asarray(ref.x)) < 1e-3


def test_every_rung_converges():
    """Walk the ladder all the way down with repeated NaN injections:
    even the final ("mgs", "ref") rung must finish the solve."""
    op, b = _op(), _rhs(48)
    tol = 1e-5
    ladder = build_ladder("cgs2_pipelined")
    ref = gmres(op, b, m=10, tol=tol, max_restarts=40)
    with faultinject.inject("core.cycle_nan", times=len(ladder) - 1):
        res, rep = gmres_self_healing(op, b, m=10, tol=tol,
                                      max_restarts=40)
    assert rep.rung == len(ladder) - 1     # bottom of the ladder
    assert rep.ladder[rep.rung] == ("mgs", "ref")
    assert not rep.gave_up and bool(res.converged)
    assert float(res.residual) <= tol * float(jnp.linalg.norm(b))
    assert int(res.restarts) - int(ref.restarts) <= 1


def test_kernel_compile_refusal_propagates(monkeypatch, tmp_path):
    """A kernel the compiler refuses is a program error: it surfaces from
    the stepped loop instead of being retried or stepped down past."""
    from repro.kernels import cgs2 as cgs2_k

    def refuse(*a, **k):
        raise ValueError("kernel lowering refused")

    monkeypatch.setattr(cgs2_k, "gs_update", refuse)
    op, b = _op(), _rhs(48)
    with pytest.raises(ValueError, match="kernel lowering refused"):
        gmres_self_healing(op, b, m=3, tol=1e-6, max_restarts=5,
                           gs="cgs2_pipelined", checkpoint_dir=tmp_path)


def test_transient_exception_absorbed_by_retries():
    op, b = _op(), _rhs(48)
    sleeps = []
    with faultinject.inject("core.cycle", at=1, times=2):
        res, rep = gmres_self_healing(op, b, m=3, tol=1e-6,
                                      max_restarts=40, max_retries=2,
                                      backoff_base=0.5,
                                      sleep=sleeps.append)
    assert bool(res.converged)
    assert rep.retries == 2 and rep.stepdowns == 0
    assert sleeps == [0.5, 1.0]            # exponential backoff, injectable


def test_exception_past_retries_costs_a_rung():
    op, b = _op(), _rhs(48)
    with faultinject.inject("core.cycle", at=1, times=3):
        res, rep = gmres_self_healing(op, b, m=3, tol=1e-6,
                                      max_restarts=40, max_retries=2)
    assert bool(res.converged)
    assert rep.retries == 2 and rep.stepdowns == 1


def test_permanent_fault_gives_up_cleanly():
    """A fault that fires at EVERY rung exhausts the ladder: gave_up is
    set, done is True, and the result carries the last good iterate."""
    op, b = _op(), _rhs(48)
    with faultinject.inject("core.cycle", times=None):
        res, rep = gmres_self_healing(op, b, m=10, tol=1e-5,
                                      max_restarts=40, max_retries=0)
    assert rep.gave_up and not bool(res.converged) and bool(res.done)
    assert rep.rung == len(rep.ladder) - 1
    assert np.isfinite(np.asarray(res.x)).all()


def test_checkpoint_resume_bit_identical(tmp_path):
    """Kill a checkpointed solve after 3 cycles (max_restarts as the
    kill switch), resume from disk: trajectory must be BIT-identical to
    an uninterrupted stepped solve."""
    op, b = _op(), _rhs(48)
    full_dir, kill_dir = str(tmp_path / "full"), str(tmp_path / "kill")
    ref, ref_rep = gmres_self_healing(op, b, m=10, tol=1e-7,
                                      max_restarts=40,
                                      checkpoint_dir=full_dir)
    assert not ref_rep.fast_path and ref_rep.checkpoints == ref_rep.cycles

    _, rep1 = gmres_self_healing(op, b, m=10, tol=1e-7, max_restarts=3,
                                 checkpoint_dir=kill_dir)
    assert rep1.cycles == 3
    res, rep2 = gmres_self_healing(op, b, m=10, tol=1e-7, max_restarts=40,
                                   checkpoint_dir=kill_dir)
    assert rep2.resumed_from == 3
    assert np.array_equal(np.asarray(res.x), np.asarray(ref.x))
    assert float(res.residual) == float(ref.residual)
    assert int(res.restarts) == int(ref.restarts)


def test_checkpoint_every_thins_writes(tmp_path):
    op, b = _op(), _rhs(48)
    _, rep = gmres_self_healing(op, b, m=10, tol=1e-7, max_restarts=40,
                                checkpoint_dir=str(tmp_path),
                                checkpoint_every=2)
    assert rep.cycles > 2
    assert rep.checkpoints == rep.cycles // 2


def test_resume_false_ignores_checkpoints(tmp_path):
    op, b = _op(), _rhs(48)
    gmres_self_healing(op, b, m=10, tol=1e-7, max_restarts=3,
                       checkpoint_dir=str(tmp_path))
    _, rep = gmres_self_healing(op, b, m=10, tol=1e-7, max_restarts=40,
                                checkpoint_dir=str(tmp_path), resume=False)
    assert rep.resumed_from is None
