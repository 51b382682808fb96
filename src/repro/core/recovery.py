"""Self-healing GMRES: degradation ladder + restart-boundary checkpoints.

The restart boundary of GMRES(m) is a FREE checkpoint: each cycle is a
pure function of the iterate x (the Krylov basis is rebuilt from the
residual at entry), so any cycle can be re-run, re-run on a different
scheme/kernel stack, or resumed after a kill, and the trajectory from a
committed x is bit-identical to an uninterrupted solve.  This module
exploits that three ways:

  detect    every committed cycle's TRUE residual feeds the bounded ring
            from ``core.gmres.Diagnostics``; ``classify_residuals`` flags
            NAN_INF / BREAKDOWN / STAGNATED against scale-relative
            thresholds (ratios only — c·A, c·b classifies identically).
  degrade   on a fault, re-run the failed cycle FROM THE LAST GOOD x one
            rung down the ladder: orthogonalization schemes step
            cgs2_pipelined -> cgs2_fused -> cgs2 -> mgs within a kernel
            mode, then the mode itself steps compiled -> interpret -> ref
            (``tuning.force_kernel_mode``) and the scheme ladder restarts.
            Transient kernel faults (exceptions) get bounded retries with
            exponential backoff BEFORE costing a rung.
  resume    with ``checkpoint_dir`` set, every committed cycle (or every
            ``checkpoint_every``-th) serializes (x, residual ring, cycle,
            rung) through ``checkpoint/checkpoint.py`` — atomic rename +
            crc32 — so a killed solve resumes from the last completed
            cycle, bit-identically.

Fault-free solves take the FUSED fast path — one plain ``gmres`` call,
zero per-cycle host round-trips — unless a fault schedule is armed for
the core sites (``runtime/faultinject.armed``) or a checkpoint/resume was
requested; only then does the solve run cycle-stepped.  The stepped loop
commits exactly the cycles the fused while_loop would, so even its
restart count matches the fast path.

``CircuitBreaker`` lives here too (the serving layer wires it around the
solver handle): closed -> open after ``threshold`` consecutive failures,
half-open trial after ``cooldown`` ticks, dead after ``max_trips`` opens
without an intervening success.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.core.gmres import (BREAKDOWN, Diagnostics, GmresResult, HEALTHY,
                              NAN_INF, STAGNATED, STATUS_NAMES,
                              classify_residuals, gmres)
from repro.core.operators import as_operator
from repro.kernels import tuning
from repro.runtime import faultinject

# Scheme half of the degradation ladder, most aggressive first.  Every
# entry is mathematically GMRES — stepping down trades collective fusion
# and kernel reliance for simplicity, never convergence semantics.
DEGRADATION_SCHEMES = ("cgs2_pipelined", "cgs2_fused", "cgs2", "mgs")


def build_ladder(gs: str = "cgs2_pipelined",
                 mode: Optional[str] = None) -> Tuple[Tuple[str, str], ...]:
    """The (scheme, kernel_mode) rung table, starting at the caller's ask.

    Schemes step down within the current kernel mode first (cheap — same
    executables family, one retrace); when they are exhausted the kernel
    mode drops to "ref" and the scheme ladder restarts from the top.  A
    compiled solve never steps into the Pallas interpreter, which would
    run on the chip at a fraction of its speed.  The final rung is always
    ("mgs", "ref") — plain jnp modified Gram-Schmidt, no kernels anywhere.
    """
    mode = tuning.kernel_mode() if mode is None else mode
    if mode not in tuning.KERNEL_MODES:
        raise ValueError(f"unknown kernel mode {mode!r}")
    rungs: List[Tuple[str, str]] = []
    for j, md in enumerate((mode,) if mode == "ref" else (mode, "ref")):
        if j == 0 and gs in DEGRADATION_SCHEMES:
            schemes = DEGRADATION_SCHEMES[DEGRADATION_SCHEMES.index(gs):]
        elif j == 0:
            # A scheme outside the ladder ("fused", "cgs", ...) is rung 0
            # as requested, then the standard ladder takes over.
            schemes = (gs,) + DEGRADATION_SCHEMES
        else:
            schemes = DEGRADATION_SCHEMES
        rungs.extend((s, md) for s in schemes)
    return tuple(rungs)


@dataclasses.dataclass
class RecoveryEvent:
    cycle: int       # committed-cycle count when the event happened
    kind: str        # "fault" | "retry" | "stepdown" | "checkpoint" | "resume"
    rung: int        # ladder index at the time
    detail: str = ""


@dataclasses.dataclass
class RecoveryReport:
    """What the self-healing loop did — attached next to the GmresResult."""
    ladder: Tuple[Tuple[str, str], ...]
    rung: int = 0                 # final ladder position
    fast_path: bool = False       # True: fused solve, nothing below applies
    cycles: int = 0               # committed restart cycles
    faults: int = 0               # detected faults (exceptions + numerical)
    retries: int = 0              # same-rung re-runs after exceptions
    stepdowns: int = 0            # rungs consumed
    checkpoints: int = 0          # checkpoint writes
    resumed_from: Optional[int] = None   # cycle a resume started from
    gave_up: bool = False         # ladder exhausted mid-fault
    events: List[RecoveryEvent] = dataclasses.field(default_factory=list)

    def log(self, cycle, kind, rung, detail=""):
        self.events.append(RecoveryEvent(cycle, kind, rung, detail))


class CircuitBreaker:
    """Tick-deterministic breaker around a repeatedly-failing callee.

    closed --threshold consecutive failures--> open (``allow`` False)
    open --cooldown ticks--> half-open (ONE trial allowed)
    half-open --success--> closed, fully reset; --failure--> open again
    More than ``max_trips`` opens without an intervening success -> dead
    (permanently open; the server fails its backlog rather than spin).
    """

    def __init__(self, threshold: int = 3, cooldown: int = 5,
                 max_trips: int = 2):
        self.threshold = threshold
        self.cooldown = cooldown
        self.max_trips = max_trips
        self.state = "closed"
        self.failures = 0          # consecutive, in closed state
        self.trips = 0             # opens since the last success
        self.open_until = 0

    @property
    def dead(self) -> bool:
        return self.state == "dead"

    def allow(self, tick: int) -> bool:
        if self.state == "open" and tick >= self.open_until:
            self.state = "half_open"
        return self.state in ("closed", "half_open")

    def record_success(self) -> None:
        if self.state != "dead":
            self.state = "closed"
            self.failures = 0
            self.trips = 0

    def record_failure(self, tick: int) -> None:
        if self.state == "dead":
            return
        self.failures += 1
        if self.state == "half_open" or self.failures >= self.threshold:
            self.trips += 1
            self.failures = 0
            if self.trips > self.max_trips:
                self.state = "dead"
            else:
                self.state = "open"
                self.open_until = tick + self.cooldown


def _checkpoint_tree(x, hist):
    return {"hist": np.asarray(hist), "x": np.asarray(x)}


def gmres_self_healing(
    a,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    m: int = 30,
    tol: float = 1e-5,
    max_restarts: int = 50,
    gs: str = "cgs2_pipelined",
    precond: Optional[Callable] = None,
    compute_dtype=None,
    window: int = 8,
    max_retries: int = 2,
    backoff_base: float = 0.0,
    sleep: Callable[[float], None] = time.sleep,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    resume: bool = True,
) -> Tuple[GmresResult, RecoveryReport]:
    """Restarted GMRES that survives kernel faults, NaNs and stagnation.

    Same solve contract as ``core.gmres.gmres`` (right-preconditioned
    GMRES(m), TRUE residual, scale-relative guards) plus the recovery
    semantics from the module docstring.  Returns ``(result, report)``;
    ``result.diagnostics`` carries the residual ring and final health
    status, ``report`` the ladder/fault/checkpoint account.

    Recovery knobs:
      window: residual-history ring length == stagnation window.
      max_retries: same-rung re-runs of a cycle whose execution RAISED
        (transient kernel fault) before the fault costs a rung.
      backoff_base: seconds for the exponential backoff between those
        retries (``backoff_base * 2**attempt`` via ``sleep`` — injectable
        for tests; 0.0 disables).
      checkpoint_dir / checkpoint_every / resume: restart-boundary
        checkpointing through ``checkpoint/checkpoint.py``; ``resume=True``
        picks up the latest complete cycle under ``checkpoint_dir``.
    """
    op = as_operator(a)
    if x0 is None:
        x0 = jnp.zeros_like(b)
    ladder = build_ladder(gs)
    report = RecoveryReport(ladder=ladder)

    stepped = (checkpoint_dir is not None
               or faultinject.armed("core.cycle", "core.cycle_nan"))
    if not stepped:
        # Fused fast path: ONE plain gmres program, the zero-overhead
        # common case.  A post-hoc HEALTHY (or converged) diagnosis means
        # nothing to recover; anything else falls through to the stepped
        # loop, re-solving from scratch one rung down — the fused solve's
        # x may be poisoned, x0 is the last x known to be good.
        res = gmres(op, b, x0, m=m, tol=tol, max_restarts=max_restarts,
                    gs=gs, precond=precond, compute_dtype=compute_dtype,
                    history=window)
        status = int(res.diagnostics.status)
        if bool(res.converged) or status in (HEALTHY, STAGNATED):
            report.fast_path = True
            report.cycles = int(res.restarts)
            return res, report
        report.faults += 1
        report.log(0, "fault", 0,
                   f"fast path diagnosed {STATUS_NAMES[status]}")
        if len(ladder) > 1:
            report.rung = 1
            report.stepdowns = 1
            report.log(0, "stepdown", 1, "->".join(ladder[1]))

    dtype = b.dtype
    bnorm = float(np.linalg.norm(np.asarray(b, np.float64)))
    tol_abs = max(tol * bnorm, 0.0)

    def true_residual(x):
        return float(jnp.linalg.norm(b - op(x)))

    x = jnp.asarray(x0)
    hist = np.full((window,), np.inf, np.float64)
    hist[-1] = true_residual(x)
    cycle = 0
    rung = report.rung
    retries = 0

    if checkpoint_dir is not None and resume:
        step = ckpt.latest_step(checkpoint_dir)
        if step is not None:
            tree, manifest = ckpt.restore(
                checkpoint_dir, _checkpoint_tree(x, hist), step=step)
            extra = manifest["extra"]
            x = jnp.asarray(tree["x"], dtype)
            hist = np.asarray(tree["hist"], np.float64)
            cycle = int(extra["cycle"])
            rung = int(extra["rung"])
            report.resumed_from = cycle
            report.log(cycle, "resume", rung, f"step {step}")

    # One compiled single-cycle solver per visited rung, traced under that
    # rung's forced kernel mode.  Each call IS one restart cycle of the
    # fused solver (pure in x), so committed trajectories are identical.
    cycle_fns = {}

    def cycle_fn(r):
        scheme, mode = ladder[r]
        if r not in cycle_fns:
            with tuning.force_kernel_mode(mode):
                cycle_fns[r] = jax.jit(lambda xx: gmres(
                    op, b, xx, m=m, tol=tol, max_restarts=1, gs=scheme,
                    precond=precond, compute_dtype=compute_dtype)
                ).lower(x).compile()
        return cycle_fns[r]

    def step_down() -> bool:
        nonlocal rung
        if rung + 1 >= len(ladder):
            return False
        rung += 1
        report.stepdowns += 1
        report.log(cycle, "stepdown", rung, "->".join(ladder[rung]))
        # Fresh stagnation window: the new rung should not be blamed for
        # (or diagnosed by) the old rung's plateau.
        hist[:-1] = np.inf
        return True

    inner_steps = 0
    beta = hist[-1]
    while beta > tol_abs and cycle < max_restarts and not report.gave_up:
        # Lowered and compiled outside the fault handler: a kernel the
        # compiler refuses is a program error, not a transient fault.
        fn = cycle_fn(rung)
        try:
            faultinject.check("core.cycle", index=cycle)
            res = fn(x)
            x_new = res.x
            beta_new = float(res.residual)
            if faultinject.fire("core.cycle_nan", index=cycle):
                beta_new = float("nan")
        except Exception as e:  # noqa: BLE001 — every execution fault lands here
            report.faults += 1
            report.log(cycle, "fault", rung, f"{type(e).__name__}: {e}")
            if retries < max_retries:
                retries += 1
                report.retries += 1
                if backoff_base > 0.0:
                    sleep(backoff_base * 2 ** (retries - 1))
                report.log(cycle, "retry", rung, f"attempt {retries}")
                continue
            retries = 0
            if not step_down():
                report.gave_up = True
            continue
        retries = 0

        cand = np.roll(hist, -1)
        cand[-1] = beta_new
        status = int(classify_residuals(jnp.asarray(cand),
                                        converged=beta_new <= tol_abs))
        if status in (NAN_INF, BREAKDOWN):
            # Poisoned or diverging cycle: DISCARD it (x stays the last
            # good iterate — the restart boundary checkpoint) and step
            # down.  No retry: the same rung would deterministically
            # reproduce a numerical fault.
            report.faults += 1
            report.log(cycle, "fault", rung, STATUS_NAMES[status])
            if not step_down():
                report.gave_up = True
            continue
        # HEALTHY or STAGNATED: the cycle is finite — commit it.
        x = x_new
        beta = beta_new
        hist = cand
        cycle += 1
        inner_steps += int(res.inner_steps)
        if status == STAGNATED:
            # Keep the (slow) progress but change the algorithm.
            report.faults += 1
            report.log(cycle, "fault", rung, "STAGNATED")
            if not step_down():
                report.log(cycle, "fault", rung, "ladder exhausted; "
                           "continuing at the final rung")
        if checkpoint_dir is not None and cycle % checkpoint_every == 0:
            ckpt.save(checkpoint_dir, cycle, _checkpoint_tree(x, hist),
                      extra={"cycle": cycle, "rung": rung,
                             "scheme": ladder[rung][0],
                             "mode": ladder[rung][1], "m": m, "tol": tol,
                             "residual": beta})
            report.checkpoints += 1
            report.log(cycle, "checkpoint", rung, f"step {cycle}")

    report.rung = rung
    report.cycles = cycle
    converged = beta <= tol_abs
    hist_j = jnp.asarray(hist, dtype)
    diags = Diagnostics(
        status=classify_residuals(hist_j, converged=converged),
        residual_history=hist_j,
        history_len=jnp.asarray(min(cycle + 1, window), jnp.int32),
    )
    result = GmresResult(
        x=x, residual=jnp.asarray(beta, dtype),
        restarts=jnp.asarray(cycle, jnp.int32),
        converged=jnp.asarray(converged),
        inner_steps=jnp.asarray(inner_steps, jnp.int32),
        done=jnp.asarray(converged | (cycle >= max_restarts)
                         | report.gave_up),
        diagnostics=diags,
    )
    return result, report
