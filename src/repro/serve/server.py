"""Host I/O loop: the impure shell around the pure scheduler.

Layering (each piece is separately testable, which is the point):

    queue.BackpressuredQueue   host ingress — bounded, blocking option
    scheduler.*                pure tick machine (admit/pack/retire)
    handles.SolverHandle       the jitted device cycle
    SolverServer               glues them: moves requests queue->lanes,
                               runs cycles, collects outcomes, keeps
                               metrics.  The ONLY code here that touches
                               a device is ``handle.cycle``.

One server serves ONE operator (the batched engine shares a single A
stream across its k lanes); the handle comes from a shared
:class:`~repro.serve.handles.HandleCache` so several servers over
different (n, fmt) buckets reuse compiled cycles instead of recompiling.

Device-side lane state is a (k, n) x block plus a (k, n) b block; a
refill overwrites ONE row of each in place (``.at[lane].set``) and
zeroes the lane's x — host work linear in n, not in k·restarts, and no
full-block device round-trip per tick.  Convergence checks read back
only the (k,) residual and inner-step vectors per tick.

Fault handling (see docs/robustness.md for the full taxonomy): the
cycle call is wrapped in bounded retries + a :class:`CircuitBreaker`
(repeatedly-failing handles stop being hammered; a dead breaker fails
the backlog instead of spinning); per-lane non-finite residuals after a
cycle evict the lane through the PURE ``scheduler.fault`` transition —
quarantine the lane, retry the occupant on a fresh lane, scrub the
poisoned device rows; per-request deadlines retire TIMEOUT; per-tick
wall times feed the ``runtime.fault_tolerance.StragglerMonitor``.  All
of it is driven deterministically by ``runtime.faultinject`` sites
(``serve.cycle``, ``serve.lane_nan``).  ``save_checkpoint`` /
``restore_checkpoint`` serialize the lane blocks + scheduler state at a
tick boundary so a killed server resumes bit-identically.

Tracing: ``submit`` and ``step`` open ``jax.profiler.TraceAnnotation``
spans named ``repro.serve.*`` (``step`` holds one child per phase:
admit, pack, dispatch, sync, retire), so a profiler trace puts the host's
phases on the device ops' clock; outside a trace a span costs about a
microsecond.  Each outcome carries the seconds its request spent queued
and in a lane (``SolveOutcome.queue_s`` / ``lane_s``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.checkpoint import checkpoint as ckpt
from repro.core.recovery import CircuitBreaker
from repro.runtime import faultinject
from repro.runtime.fault_tolerance import StragglerMonitor
from repro.serve import scheduler as sched
from repro.serve.handles import HandleCache, SolverHandle
from repro.serve.queue import BackpressuredQueue
from repro.serve.request import (AdmissionError, FAILED, REJECTED, TIMEOUT,
                                 SolveOutcome, SolveRequest, validate_b,
                                 validate_params, validate_precond)


class SolverServer:
    """Continuous-batching GMRES server over one operator.

    >>> srv = SolverServer(op, m=16, k=8)
    >>> rid = srv.submit(b, tol=1e-5, max_restarts=40)
    >>> srv.run()                        # drain queue + lanes
    >>> out = srv.results[rid]           # SolveOutcome(status='done', ...)
    """

    def __init__(self, op, *, m: int = 30, k: int = 8,
                 dtype=jnp.float32, gs: str = "cgs2", precond=None,
                 max_pending: int = 64, queue_depth: Optional[int] = None,
                 handle_cache: Optional[HandleCache] = None,
                 clock=time.monotonic, sleep=time.sleep,
                 deadline_default: Optional[int] = None,
                 quarantine_ticks: int = 2, fault_retries: int = 1,
                 cycle_retries: int = 2, backoff_base: float = 0.0,
                 breaker_threshold: int = 3, breaker_cooldown: int = 5,
                 breaker_max_trips: int = 2,
                 straggler_window: int = 50, straggler_zscore: float = 3.0):
        # Precond/operator mismatch is rejected HERE, before a handle
        # exists: it is the one parameter a per-request gate cannot
        # catch, and letting it through fails inside a jitted lane.
        validate_precond(precond, op)
        cache = handle_cache if handle_cache is not None else HandleCache()
        self.handle: SolverHandle = cache.get(op, m=m, k=k, dtype=dtype,
                                              gs=gs, precond=precond)
        self.handle_cache = cache
        self.state = sched.init(k, max_pending=max_pending)
        self.ingress = BackpressuredQueue(
            max_depth=queue_depth if queue_depth is not None else max_pending)
        self.results: Dict[int, SolveOutcome] = {}
        self._clock = clock
        self._sleep = sleep
        self._next_rid = 0
        # rid -> submit time and first placement, on ``clock``, until the
        # request's outcome is recorded.
        self._submitted: Dict[int, float] = {}
        self._placed: Dict[int, float] = {}
        # --- fault-handling knobs / state ------------------------------
        self._deadline_default = deadline_default
        self._quarantine_ticks = int(quarantine_ticks)
        self._fault_retries = int(fault_retries)
        self._cycle_retries = int(cycle_retries)
        self._backoff_base = float(backoff_base)
        # The breaker is clocked by step() INVOCATIONS, not scheduler
        # ticks: a failed cycle never advances the scheduler tick, so the
        # cooldown would otherwise wait on a clock that stopped.
        self.breaker = CircuitBreaker(threshold=breaker_threshold,
                                      cooldown=breaker_cooldown,
                                      max_trips=breaker_max_trips)
        self.straggler = StragglerMonitor(window=straggler_window,
                                          zscore=straggler_zscore)
        self._steps = 0               # breaker clock
        self.cycle_faults = 0         # cycle attempts that raised
        self.breaker_skips = 0        # steps skipped while cooling down
        self._last_cycle_error = ""
        # Device-side lane blocks (jnp so cycles never re-upload idle rows).
        kk, n = self.handle.block_shape()
        dt = jnp.dtype(self.handle.key.dtype)
        self._b = jnp.zeros((kk, n), dt)
        self._x = jnp.zeros((kk, n), dt)
        self._tol_abs = np.zeros(kk, np.float64)
        self._inner = np.zeros(kk, np.int64)   # Arnoldi steps per occupant

    # ------------------------------------------------------------------
    # Admission (host ingress)
    # ------------------------------------------------------------------
    def submit(self, b, *, tol: float = 1e-5, max_restarts: int = 50,
               deadline_ticks: Optional[int] = None,
               wait: bool = False, max_wait: float = 1.0) -> int:
        """Admit one solve; returns its rid.

        Invalid b (NaN/Inf, wrong n, non-real dtype) and invalid solver
        parameters (non-finite/non-positive tol, max_restarts < 1, a
        non-positive deadline) are REJECTED here — they never enter the
        queue, so they can never poison a lane block or wedge the tick
        loop.  A full queue refuses non-blocking submits the same way;
        ``wait=True`` instead drains the backlog by ticking the scheduler
        (bounded by ``max_wait``): the server is single-threaded, so the
        submitter IS the consumer — sleeping for someone else to pop the
        ingress would wait forever.

        ``deadline_ticks``: retire TIMEOUT after this many lane ticks
        (defaults to the server's ``deadline_default``; None = none).
        """
        rid = self._next_rid
        self._next_rid += 1
        with TraceAnnotation("repro.serve.submit", rid=rid):
            t_submit = self._clock()
            if deadline_ticks is None:
                deadline_ticks = self._deadline_default
            if self.breaker.dead:
                self._finish(rid, REJECTED,
                             reason="circuit breaker open: solver handle is "
                                    f"failing ({self._last_cycle_error})")
                return rid
            try:
                validate_params(tol, max_restarts, deadline_ticks)
                arr = validate_b(b, n=self.handle.n,
                                 dtype=self.handle.key.dtype)
            except AdmissionError as e:
                self._finish(rid, REJECTED, reason=e.reason)
                return rid
            # Quantize the retirement threshold to the handle's compute
            # dtype: the compiled cycle masks lanes with the downcast
            # tol_abs, and host retirement must agree on "converged" or a
            # lane can wedge between the two thresholds (device says done,
            # host keeps charging restarts until the budget fails it).
            dt = np.dtype(self.handle.key.dtype)
            tol_abs = float(np.asarray(float(tol) * np.linalg.norm(arr), dt))
            req = SolveRequest(rid=rid, b=arr, tol=float(tol),
                               max_restarts=int(max_restarts),
                               deadline_ticks=(None if deadline_ticks is None
                                               else int(deadline_ticks)),
                               tol_abs_override=tol_abs)
            if wait:
                deadline = self._clock() + max_wait
                while self.ingress.full and self._clock() < deadline:
                    depth = len(self.ingress)
                    self.step()              # we are our own consumer
                    if len(self.ingress) >= depth:
                        # Tick freed no headroom (lanes mid-solve, backlog
                        # full): yield real time toward the deadline.
                        self._sleep(0.01)
            if self.ingress.push(req):
                self._submitted[rid] = t_submit
            else:
                self._finish(
                    rid, REJECTED,
                    reason=f"backpressure: queue depth {len(self.ingress)} "
                           f">= {self.ingress.max_depth}")
        return rid

    # ------------------------------------------------------------------
    # The tick loop
    # ------------------------------------------------------------------
    def _admit_from_ingress(self) -> None:
        while self.ingress.peek() is not None:
            st, ok = sched.admit(self.state, self.ingress.peek())
            if not ok:
                break                    # pending full: leave it queued
            self.state = st
            self.ingress.pop()

    def _pack(self) -> List[tuple]:
        """Fill idle lanes; returns the placements ``(lane, request)``."""
        self.state, placed = sched.pack(self.state)
        # Row-wise device updates: only the refilled lanes move — the
        # resident lanes' b/x never round-trip through the host.
        dt = self._b.dtype
        now = self._clock()
        for lane, req in placed:
            self._b = self._b.at[lane].set(jnp.asarray(req.b, dt))
            self._x = self._x.at[lane].set(0.0)
            self._tol_abs[lane] = req.tol_abs
            self._inner[lane] = 0
            self._placed.setdefault(req.rid, now)
        return placed

    def _finish(self, rid: int, status: str, **fields) -> None:
        """Record ``rid``'s outcome, with its queue and lane seconds."""
        t_submit = self._submitted.pop(rid, None)
        t_placed = self._placed.pop(rid, None)
        if t_placed is not None:
            fields["lane_s"] = self._clock() - t_placed
            if t_submit is not None:
                fields["queue_s"] = t_placed - t_submit
        self.results[rid] = SolveOutcome(rid=rid, status=status, **fields)

    def _scrub_lane(self, i: int) -> None:
        """Zero a faulted lane's device rows: NaN in a retired lane's x
        row is confined to that lane's GEMM column, but a zeroed row costs
        nothing and removes the poison from every later block readback."""
        self._b = self._b.at[i].set(0.0)
        self._x = self._x.at[i].set(0.0)
        self._tol_abs[i] = 0.0
        self._inner[i] = 0

    def _fail_backlog(self, reason: str) -> List[sched.Retirement]:
        """Terminal breaker path: retire EVERYTHING as FAILED.

        A dead breaker means the handle cannot run cycles at all; without
        this, ``run()`` would spin its max_ticks bound with lanes wedged
        mid-solve.  Every in-flight and queued request gets a FAILED
        outcome carrying the last cycle error."""
        retired: List[sched.Retirement] = []
        self._admit_from_ingress()
        lanes = list(self.state.lanes)
        occupants = [(i, ln) for i, ln in enumerate(lanes) if not ln.idle]
        for i, ln in occupants:
            retired.append(sched.Retirement(
                lane=i, req=ln.req, status=FAILED, residual=float("inf"),
                restarts=ln.restarts, reason=reason))
            lanes[i] = sched.Lane()
            self._scrub_lane(i)
        pending = self.state.pending
        for req in pending:
            retired.append(sched.Retirement(
                lane=-1, req=req, status=FAILED, residual=float("inf"),
                restarts=0, reason=reason))
        self.state = dataclasses.replace(
            self.state, lanes=tuple(lanes), pending=(),
            retired_failed=self.state.retired_failed + len(retired))
        for r in retired:
            self._finish(r.req.rid, FAILED, residual=float("inf"),
                         restarts=r.restarts, reason=reason)
        return retired

    def step(self) -> List[sched.Retirement]:
        """ONE scheduler tick: admit, pack, cycle, detect faults, retire.

        Returns the retirements (fault-FAILED evictions included) so
        callers and tests can watch lanes free up.  The cycle call gets
        ``cycle_retries`` bounded retries with exponential backoff — a
        transient kernel fault costs latency, not state — then a breaker
        failure; while the breaker cools down, steps admit but run no
        cycle; a DEAD breaker fails the whole backlog (once) instead of
        wedging ``run()``.

        A profiler trace shows the tick as a ``repro.serve.step`` span
        whose children are its phases: ``admit``, ``pack`` (metadata: the
        lanes and rids placed), ``dispatch`` (the jitted cycle call, which
        returns before the device finishes), ``sync`` (the host waiting
        for the cycle's residuals) and ``retire`` (metadata: the rids
        retired).
        """
        with TraceAnnotation("repro.serve.step"):
            return self._tick()

    def _tick(self) -> List[sched.Retirement]:
        t_start = self._clock()
        self._steps += 1
        if self.breaker.dead:
            return self._fail_backlog(
                f"circuit breaker open permanently ({self._last_cycle_error})")
        with TraceAnnotation("repro.serve.admit"):
            self._admit_from_ingress()
        if not self.breaker.allow(self._steps):
            self.breaker_skips += 1
            return []
        with TraceAnnotation("repro.serve.pack") as span:
            placed = self._pack()
            if placed and span.is_enabled():
                span.set_metadata(lanes=[lane for lane, _ in placed],
                                  rids=[req.rid for _, req in placed])
        active = np.array([not ln.idle for ln in self.state.lanes])
        if not active.any():
            return []
        out = self._run_cycle(active)
        if out is None:
            return []
        with TraceAnnotation("repro.serve.retire") as span:
            retired = self._collect(*out, active)
            if retired and span.is_enabled():
                span.set_metadata(rids=[r.req.rid for r in retired])
        self.straggler.record(self.state.tick, self._clock() - t_start)
        return retired

    def _run_cycle(self, active: np.ndarray) -> Optional[tuple]:
        """Run the lockstep cycle with bounded retries; ``(x, beta,
        inner)``, or None when the retries ran out."""
        attempt = 0
        while True:
            try:
                faultinject.check("serve.cycle", index=self.state.tick)
                with TraceAnnotation("repro.serve.dispatch"):
                    x, beta, inner = self.handle.cycle(
                        self._b, self._x,
                        np.where(active, self._tol_abs, 0.0), active)
                with TraceAnnotation("repro.serve.sync"):
                    beta = np.array(beta)   # materialize: surface faults HERE
                break
            except Exception as e:  # noqa: BLE001 — injected + kernel faults
                self.cycle_faults += 1
                self._last_cycle_error = f"{type(e).__name__}: {e}"
                if attempt < self._cycle_retries:
                    attempt += 1
                    if self._backoff_base > 0.0:
                        self._sleep(self._backoff_base * 2 ** (attempt - 1))
                    continue
                # Retries exhausted: this tick is a no-op (device blocks
                # and scheduler state untouched — the restart boundary IS
                # the rollback) and the breaker hears about it.
                self.breaker.record_failure(self._steps)
                return None
        self.breaker.record_success()
        return x, beta, inner

    def _collect(self, x, beta: np.ndarray, inner,
                 active: np.ndarray) -> List[sched.Retirement]:
        """Take a cycle's results: evict faulted lanes, retire finished
        ones and copy their answers to the host."""
        if faultinject.fire("serve.lane_nan", index=self.state.tick):
            i = int(np.argmax(active))      # lowest-indexed active lane
            x = x.at[i].set(jnp.nan)
            beta[i] = np.nan

        self._x = x
        self._inner += np.where(active, np.asarray(inner), 0)

        # Lane-level fault detection: a non-finite post-cycle residual
        # means that lane's arithmetic is poisoned.  Evict through the
        # pure fault transition (quarantine + retry-on-fresh-lane), scrub
        # the device rows, and only then run normal retirement.
        fault_retired: List[sched.Retirement] = []
        bad = active & ~np.isfinite(beta)
        if bad.any():
            idx = [int(i) for i in np.nonzero(bad)[0]]
            self.state, _requeued, failed = sched.fault(
                self.state, idx, quarantine_ticks=self._quarantine_ticks,
                max_retries=self._fault_retries)
            for i in idx:
                self._scrub_lane(i)
            for r in failed:
                self._finish(r.req.rid, FAILED, residual=float("inf"),
                             restarts=r.restarts, reason=r.reason)
            fault_retired = failed

        self.state, retired = sched.retire(self.state, beta)
        if retired:
            x_host = np.asarray(self._x)
            for r in retired:
                self._finish(r.req.rid, r.status,
                             x=x_host[r.lane].copy(), residual=r.residual,
                             restarts=r.restarts,
                             inner_steps=int(self._inner[r.lane]),
                             reason=r.reason)
        return fault_retired + retired

    def run(self, max_ticks: int = 10_000) -> int:
        """Tick until queue, backlog and lanes are all drained.

        Returns the number of ticks run.  ``max_ticks`` is a safety
        bound, not a policy: per-lane budgets guarantee every occupant
        retires in at most its own ``max_restarts`` ticks.
        """
        ticks = 0
        while (self.state.busy or self.ingress.peek() is not None):
            if ticks >= max_ticks:
                raise RuntimeError(
                    f"server did not drain in {max_ticks} ticks "
                    f"({sched.metrics(self.state)})")
            self.step()
            ticks += 1
        return ticks

    # ------------------------------------------------------------------
    # Checkpoint / resume (restart-boundary, tick-aligned)
    # ------------------------------------------------------------------
    @staticmethod
    def _req_meta(req: SolveRequest) -> dict:
        return {"rid": req.rid, "tol": req.tol,
                "max_restarts": req.max_restarts,
                "tol_abs_override": req.tol_abs_override,
                "deadline_ticks": req.deadline_ticks,
                "retries": req.retries}

    @staticmethod
    def _req_from(meta: dict, b: np.ndarray) -> SolveRequest:
        return SolveRequest(
            rid=int(meta["rid"]), b=np.asarray(b), tol=float(meta["tol"]),
            max_restarts=int(meta["max_restarts"]),
            tol_abs_override=(None if meta["tol_abs_override"] is None
                              else float(meta["tol_abs_override"])),
            deadline_ticks=(None if meta["deadline_ticks"] is None
                            else int(meta["deadline_ticks"])),
            retries=int(meta["retries"]))

    def save_checkpoint(self, directory: str) -> str:
        """Serialize lanes + backlog at the current tick boundary.

        Everything a resumed server needs to continue bit-identically:
        the device b/x blocks (the lane iterates ARE the solve state —
        each cycle is a pure function of them), per-lane budgets and
        tol_abs, the full scheduler state including quarantine, queued
        and in-queue request metadata, and the rid counter.  Goes through
        ``checkpoint/checkpoint.py`` (atomic rename + crc32); call it
        between ticks — mid-``step`` there is no consistent boundary.
        Returns the checkpoint path.
        """
        st = self.state
        n = self.handle.n
        stack = (lambda reqs: np.stack([np.asarray(r.b, np.float64)
                                        for r in reqs])
                 if reqs else np.zeros((0, n), np.float64))
        tree = {
            "b": np.asarray(self._b),
            "ingress_b": stack(list(self.ingress.items)),
            "inner": self._inner.copy(),
            "pending_b": stack(list(st.pending)),
            "tol_abs": self._tol_abs.copy(),
            "x": np.asarray(self._x),
        }
        extra = {
            "lanes": [None if ln.idle else self._req_meta(ln.req)
                      for ln in st.lanes],
            "lane_restarts": [ln.restarts for ln in st.lanes],
            "pending": [self._req_meta(r) for r in st.pending],
            "ingress": [self._req_meta(r) for r in self.ingress.items],
            "sched": {
                "tick": st.tick, "admitted": st.admitted,
                "rejected": st.rejected, "retired_done": st.retired_done,
                "retired_failed": st.retired_failed,
                "retired_timeout": st.retired_timeout,
                "lane_faults": st.lane_faults, "requeued": st.requeued,
                "lane_cycles": st.lane_cycles,
                "max_pending": st.max_pending,
                "quarantine": list(st.quarantine),
            },
            "next_rid": self._next_rid,
            "k": st.k, "n": n, "m": self.handle.key.m,
            "dtype": str(self.handle.key.dtype),
        }
        return ckpt.save(directory, st.tick, tree, extra=extra)

    def restore_checkpoint(self, directory: str,
                           step: Optional[int] = None) -> "SolverServer":
        """Rebuild lanes + backlog from ``save_checkpoint`` output.

        The server must have been constructed over the same operator
        geometry (k, n, m, dtype) — the handle itself is re-lowered, not
        serialized (compiled executables don't survive processes; the
        cycle they compile to is deterministic).  In-flight lanes resume
        from their checkpointed x — every subsequent cycle is the pure
        function of (b, x, tol_abs) it always is, so outcomes match an
        uninterrupted run bit-for-bit.  Returns self.
        """
        kk, n = self.handle.block_shape()
        tree_like = {
            "b": np.zeros((kk, n)), "ingress_b": np.zeros((0, n)),
            "inner": np.zeros(kk), "pending_b": np.zeros((0, n)),
            "tol_abs": np.zeros(kk), "x": np.zeros((kk, n)),
        }
        tree, manifest = ckpt.restore(directory, tree_like, step=step)
        extra = manifest["extra"]
        if (extra["k"], extra["n"]) != (kk, n) \
                or extra["m"] != self.handle.key.m \
                or extra["dtype"] != str(self.handle.key.dtype):
            raise ValueError(
                f"checkpoint geometry (k={extra['k']}, n={extra['n']}, "
                f"m={extra['m']}, {extra['dtype']}) does not match this "
                f"server's handle (k={kk}, n={n}, m={self.handle.key.m}, "
                f"{self.handle.key.dtype})")
        dt = jnp.dtype(self.handle.key.dtype)
        self._b = jnp.asarray(tree["b"], dt)
        self._x = jnp.asarray(tree["x"], dt)
        self._tol_abs = np.asarray(tree["tol_abs"], np.float64)
        self._inner = np.asarray(tree["inner"], np.int64)
        b_host = np.asarray(tree["b"])
        lanes = tuple(
            sched.Lane() if meta is None
            else sched.Lane(req=self._req_from(meta, b_host[i]),
                            restarts=int(extra["lane_restarts"][i]))
            for i, meta in enumerate(extra["lanes"]))
        pending = tuple(self._req_from(meta, tree["pending_b"][i])
                        for i, meta in enumerate(extra["pending"]))
        ss = extra["sched"]
        self.state = sched.SchedulerState(
            lanes=lanes, pending=pending,
            max_pending=int(ss["max_pending"]), tick=int(ss["tick"]),
            quarantine=tuple(int(q) for q in ss["quarantine"]),
            admitted=int(ss["admitted"]), rejected=int(ss["rejected"]),
            retired_done=int(ss["retired_done"]),
            retired_failed=int(ss["retired_failed"]),
            retired_timeout=int(ss["retired_timeout"]),
            lane_faults=int(ss["lane_faults"]),
            requeued=int(ss["requeued"]),
            lane_cycles=int(ss["lane_cycles"]))
        self.ingress = BackpressuredQueue(max_depth=self.ingress.max_depth)
        for i, meta in enumerate(extra["ingress"]):
            self.ingress.push(self._req_from(meta, tree["ingress_b"][i]))
        self._next_rid = int(extra["next_rid"])
        # Stamps are on this process's clock and are not saved: restored
        # requests report no queue or lane seconds.
        self._submitted.clear()
        self._placed.clear()
        return self

    # ------------------------------------------------------------------
    # Observability
    # ------------------------------------------------------------------
    def metrics(self) -> dict:
        """Scheduler counters + ingress + handle-cache + fault state +
        retirements per tick.  ``cycle_traces`` counts how often the
        handle's cycle was traced; above 1, a tick recompiled it."""
        m = sched.metrics(self.state)
        m.update({
            "ingress_depth": len(self.ingress),
            "ingress_refused": self.ingress.refused,
            "handle_cache": self.handle_cache.stats(),
            "cycles_run": self.handle.cycles_run,
            "cycle_faults": self.cycle_faults,
            "breaker_state": self.breaker.state,
            "breaker_trips": self.breaker.trips,
            "breaker_skips": self.breaker_skips,
            "straggler_ticks": len(self.straggler.flagged),
            "cycle_traces": self.handle.traces,
            "retirement_rate": ((m["retired_done"] + m["retired_failed"])
                                / m["tick"] if m["tick"] else 0.0),
        })
        return m
