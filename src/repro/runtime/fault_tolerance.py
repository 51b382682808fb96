"""Fault-tolerant training runner: checkpoint/restart, elastic re-meshing,

straggler detection.  The runner owns the outer loop a 1000-node deployment
needs:

  - periodic async checkpoints (off the step critical path)
  - on ANY step failure: restore the last complete checkpoint, rebuild the
    mesh from the surviving device set (elastic: the data axis shrinks, the
    model axis is preserved — TP degree is a numerics contract, DP is not),
    re-lower the step, resume from the restored step with the SAME data
    stream (the pipeline is a pure function of (seed, step, host))
  - straggler monitor: per-step wall-time z-score; persistent outliers
    raise a hook the cluster layer maps to "demote host / promote spare"

The device-failure path is exercised in tests via an injected fault (a step
function that raises on a chosen step) plus a shrunken fake-device mesh —
the same code path a real XLA `DataLoss`/halt error takes.
"""
from __future__ import annotations

import collections
import logging
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import numpy as np

from repro.checkpoint import checkpoint as ckpt

log = logging.getLogger("repro.runtime")


class StragglerMonitor:
    """Flags steps (and, across restarts, hosts) with outlier wall-times."""

    def __init__(self, window: int = 50, zscore: float = 3.0,
                 min_samples: int = 10):
        self.window = window
        self.zscore = zscore
        self.min_samples = min_samples
        # The last ``window`` step times: the baseline a step is judged by.
        self.times: collections.deque = collections.deque(maxlen=window)
        self.flagged: list = []

    def record(self, step: int, dt: float) -> bool:
        """Returns True if this step is a straggler."""
        hist = list(self.times)
        self.times.append(dt)
        if len(hist) < self.min_samples:
            return False
        mu = float(np.mean(hist))
        sd = float(np.std(hist)) + 1e-9
        if (dt - mu) / sd > self.zscore:
            self.flagged.append((step, dt, mu))
            return True
        return False


@dataclass
class RunnerConfig:
    checkpoint_dir: str = "/tmp/repro_ckpt"
    checkpoint_every: int = 100
    keep_checkpoints: int = 3
    max_failures: int = 3
    straggler_window: int = 50


@dataclass
class Runner:
    """Owns the fault-tolerant outer loop.

    build_step(mesh) -> step_fn(state, batch) -> (state, metrics): re-invoked
    after every elastic re-mesh so shardings re-bind to the new topology.
    make_mesh(n_failures) -> mesh: the elasticity policy (see elastic.py).
    """
    config: RunnerConfig
    make_mesh: Callable[[int], Any]
    build_step: Callable[[Any], Callable]
    init_state: Callable[[Any], Any]        # mesh -> train state pytree
    batch_for: Callable[[int, Any], Any]    # (step, mesh) -> device batch

    monitor: StragglerMonitor = field(default_factory=StragglerMonitor)
    failures: int = 0

    def run(self, num_steps: int, *, state=None,
            on_metrics: Optional[Callable] = None):
        cp = ckpt.AsyncCheckpointer(self.config.checkpoint_dir)
        mesh = self.make_mesh(self.failures)
        step_fn = self.build_step(mesh)
        if state is None:
            state = self.init_state(mesh)
        start = 0
        restored = self._try_restore(state)
        if restored is not None:
            state, start = restored
            log.info("restored checkpoint at step %d", start)

        step = start
        while step < num_steps:
            try:
                t0 = time.perf_counter()
                batch = self.batch_for(step, mesh)
                state, metrics = step_fn(state, batch)
                dt = time.perf_counter() - t0
                if self.monitor.record(step, dt):
                    log.warning("straggler step %d: %.3fs", step, dt)
                if on_metrics:
                    on_metrics(step, metrics, dt)
                step += 1
                if step % self.config.checkpoint_every == 0:
                    cp.save_async(step, state, extra={"step": step})
                    ckpt.cleanup(self.config.checkpoint_dir,
                                 self.config.keep_checkpoints)
            except Exception as e:   # device loss / injected fault
                self.failures += 1
                log.error("step %d failed (%s); failure %d/%d", step, e,
                          self.failures, self.config.max_failures)
                if self.failures > self.config.max_failures:
                    raise
                cp.wait()
                # elastic re-mesh: data axis may shrink; model axis fixed
                mesh = self.make_mesh(self.failures)
                step_fn = self.build_step(mesh)
                state = self.init_state(mesh)
                restored = self._try_restore(state)
                if restored is not None:
                    state, step = restored
                else:
                    step = start   # no checkpoint yet: replay from scratch
                log.info("resumed at step %d on %s", step,
                         dict(mesh.shape) if hasattr(mesh, "shape")
                         else mesh)
        cp.wait()
        cp.save_async(step, state, extra={"step": step})
        cp.wait()
        return state, step

    def _try_restore(self, state_like):
        step = ckpt.latest_step(self.config.checkpoint_dir)
        if step is None:
            return None
        state, manifest = ckpt.restore(self.config.checkpoint_dir,
                                       state_like, step=step)
        return state, manifest["extra"].get("step", step)
