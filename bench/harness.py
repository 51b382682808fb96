"""One run of one cell: set-up, the measured window, the check, the result.

The harness knows two loops and nothing of any one cell.  A cell of
``BENCHMARK.json`` names a configuration (its file holds the sizes, the
solver's settings, the limits of the check, and the system family that
builds it) and a traffic mix (``bench/traffic/<name>.json``, read by
``generator``).  Each metric is a reader of its own,
``bench/metrics/<name>.py``.  A later cell or metric is new files and new
entries, never an edit here.

  closed loop   one caller; whole solves back to back through the
                program's ``gmres``, right-hand sides from a seeded pool
                or, for a time stepper (``chain``), each solve's answer
                the next one's right-hand side and first guess, until
                ``seconds`` have passed, then the solve in progress is
                finished;
  open loop     requests due on a seeded schedule are submitted to the
                program's ``SolverServer`` whether or not it keeps up; the
                window holds the requests due in it, and after it closes
                the server is drained for up to ``DRAIN_S`` seconds.

A cell whose ``chips`` is above 1 runs the closed loop through the
program's row-sharded path: a one-axis mesh over that many devices, the
family's arrays placed by its ``shard_specs``, and the program's ``gmres``
called per shard with ``axis_name`` inside ``tuning.shard_context``, as
``core/distributed.py`` calls it.  The cell's ``chips`` is the only switch.

After the window: peak device memory is read, the program's state is
dropped, and a seeded sample of the answers the window produced is checked
on the host in float64 against the configuration's plain reference.
"""
from __future__ import annotations

import copy
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import generator, roofline, trace as trace_mod

BENCH_DIR = pathlib.Path(__file__).resolve().parent
DRAIN_S = 60.0
# A traced run profiles the window's first TRACE_S seconds (to the end of
# the solve or tick in progress): enough for thousands of steps, and a
# trace that reads back in seconds.
TRACE_S = 5.0
# A time stepper starts again from its first state every CHAIN_STEPS
# steps (the warm-up is step 0): after about 1150 backward-Euler steps
# the 1024^2 stepper's field underflows to zero, and a zero right-hand
# side has no relative residual to check.
CHAIN_STEPS = 1024
# The mesh axis of a cell on several chips.
AXIS = "rows"

# The loops' clock, which the tests replace.
_now = time.perf_counter
_sleep = time.sleep


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------------
# Finding a cell's files by name
# --------------------------------------------------------------------------
def _load_module(path: pathlib.Path):
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Layout:
    """``BENCHMARK.json`` under ``root`` and the files its names lead to.

    Traffic, system families and metric readers are looked up under
    ``root/bench`` first and then beside this file, so a checkout (or a
    test) can add its own.
    """

    def __init__(self, root):
        self.root = pathlib.Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _find(self, kind: str, name: str, ext: str) -> pathlib.Path:
        for base in (self.root / "bench" / kind, BENCH_DIR / kind):
            path = base / f"{name}{ext}"
            if path.is_file():
                return path
        raise FileNotFoundError(f"no {kind} file for {name!r}")

    def cell(self, name: str) -> dict:
        for cell in self.spec["workloads"]:
            if cell["name"] == name:
                return cell
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[c['name'] for c in self.spec['workloads']]}")

    def config(self, cell: dict) -> dict:
        for cfg in self.spec["configs"]:
            if cfg["name"] == cell["config"]:
                return json.loads((self.root / cfg["file"]).read_text())
        raise KeyError(f"no config {cell['config']!r}")

    def traffic(self, cell: dict) -> dict:
        return json.loads(self._find("traffic", cell["traffic"],
                                     ".json").read_text())

    def family(self, cfg: dict):
        return _load_module(self._find("configs", cfg["system"], ".py"))

    def reader(self, metric: str):
        return _load_module(self._find("metrics", metric, ".py"))

    def metrics(self, cell: dict, traced: bool) -> List[dict]:
        """The cell's end-to-end metrics, or with ``traced`` its per-layer
        metrics, in the order ``BENCHMARK.json`` lists them."""
        name = cell["name"]
        e2e = [m for m in self.spec["end_to_end"]
               if name in m.get("workloads", [name])]
        if not traced:
            return e2e
        moves = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if (name in m["workloads"] if "workloads" in m
                    else m["moves"] in moves)]


# --------------------------------------------------------------------------
# What a metric reader sees
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    shapes: dict
    peaks: Optional[dict]
    setup_s: float = 0.0
    window_s: float = 0.0
    solves: List[dict] = dataclasses.field(default_factory=list)
    requests: List[dict] = dataclasses.field(default_factory=list)
    ticks: List[dict] = dataclasses.field(default_factory=list)
    traced_cycles: List[List[int]] = dataclasses.field(default_factory=list)
    trace: Optional[trace_mod.Summary] = None


# --------------------------------------------------------------------------
# Device
# --------------------------------------------------------------------------
def device_info(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count of the devices; NoChip without a TPU or
    with fewer chips than asked, UnknownDevice for a kind with no peaks."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
        if len(devs) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
        roofline.peaks(kind)
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileCounter:
    """Counts JAX compilations (and persistent-cache reads) while open."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.open = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_args, **_kw):
        if self.open and name in self.EVENTS:
            self.count += 1


# --------------------------------------------------------------------------
# A cell on several chips
# --------------------------------------------------------------------------
def sharded_mesh(family, cfg: dict, traffic: dict, chips: int):
    """A one-axis mesh over the first ``chips`` devices, for a cell whose
    family declares ``shard_specs`` and whose traffic is a closed loop."""
    import jax
    from jax.sharding import Mesh

    if not hasattr(family, "shard_specs"):
        raise ValueError(
            f"the cell asks for {chips} chips, but the {cfg['system']!r} "
            f"family declares no shard_specs(cfg, axis): it runs on one chip")
    if traffic["loop"] != "closed":
        raise ValueError(
            f"the cell asks for {chips} chips, but its traffic runs the "
            f"{traffic['loop']!r} loop, which runs on one chip; only the "
            f"closed loop runs the program's sharded solve")
    return Mesh(np.asarray(jax.devices()[:chips]), (AXIS,))


def place(family, cfg: dict, mesh, arrays: dict, pool: list):
    """The family's arrays placed by its ``shard_specs``, and each pool
    vector row-sharded over the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    specs = family.shard_specs(cfg, AXIS)
    arrays = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in arrays.items()}
    row = NamedSharding(mesh, P(AXIS))
    return arrays, [jax.device_put(v, row) for v in pool]


# --------------------------------------------------------------------------
# The timed paths
# --------------------------------------------------------------------------
def make_solve(family, cfg: dict, traffic: dict, arrays: dict, mesh=None):
    """The program's ``gmres`` as one jitted call, and its fixed arguments.

    A preconditioner named by the traffic is built once, in set-up, through
    the program's own ``make_preconditioner``; the timed call applies that
    object's own ``__call__`` with its arrays passed as jit arguments.
    With a ``mesh`` (a cell on several chips) see ``make_sharded_solve``.
    """
    if mesh is not None:
        return make_sharded_solve(family, cfg, traffic, arrays, mesh)
    import jax

    import repro.core as core
    from repro.core import preconditioners

    kw = _solver_kw(cfg, traffic)
    name = traffic.get("precond")
    if name is None:
        def solve(arrays, b, x0):
            return core.gmres(family.program_operator(cfg, arrays), b, x0,
                              **kw)
        return jax.jit(solve), (arrays,)

    made = []

    def factor(arrays):
        pc = preconditioners.make_preconditioner(
            name, family.program_operator(cfg, arrays))
        made.append(pc)
        return {k: v for k, v in vars(pc).items()
                if isinstance(v, jax.Array)}

    state = jax.block_until_ready(jax.jit(factor)(arrays))
    template = made[0]

    def solve(arrays, state, b, x0):
        op = family.program_operator(cfg, arrays)
        return core.gmres(op, b, x0, precond=_bind(template, state, op),
                          **kw)

    return jax.jit(solve), (arrays, state)


def make_sharded_solve(family, cfg: dict, traffic: dict, arrays: dict,
                       mesh):
    """``make_solve`` for a cell on several chips: the program's
    row-sharded path.

    Each call runs per shard, a ``shard_map`` over ``AXIS`` with the
    family's ``shard_specs``, traced inside ``tuning.shard_context``: the
    shared cycle that ``distributed.gmres_sharded`` wraps, the program's
    ``gmres`` with ``axis_name``.  The preconditioner is built in set-up
    from each shard's own block of the operator (block-Jacobi over the
    shards; ILU(0) of each block for ``banded_block_jacobi``); its arrays
    come back with a leading axis of one entry a shard, whatever their own
    layout, and each stays on its shard.  ``x`` comes back row-sharded.
    """
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    import repro.core as core
    from repro.core import preconditioners
    from repro.core.gmres import GmresResult
    from repro.kernels import tuning

    chips = mesh.shape[AXIS]
    specs, vec = family.shard_specs(cfg, AXIS), P(AXIS)
    # As distributed._run_sharded: x row-sharded, the rest replicated.
    result = GmresResult(*(P() for _ in GmresResult._fields))._replace(x=vec)
    kw = dict(_solver_kw(cfg, traffic), axis_name=AXIS)

    def per_shard(fn, in_specs, out_specs):
        @functools.wraps(fn)
        def local(*args):
            with tuning.shard_context(AXIS, chips):
                return fn(*args)
        return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    name = traffic.get("precond")
    if name is None:
        def solve(arrays, b, x0):
            return core.gmres(family.program_operator(cfg, arrays), b, x0,
                              **kw)
        return per_shard(solve, (specs, vec, vec), result), (arrays,)

    made = []

    def factor(arrays):
        pc = preconditioners.make_preconditioner(
            name, family.program_operator(cfg, arrays))
        made.append(pc)
        return {k: v[None] for k, v in vars(pc).items()
                if isinstance(v, jax.Array)}

    state = jax.block_until_ready(per_shard(factor, (specs,), vec)(arrays))
    template = made[0]

    def solve(arrays, state, b, x0):
        op = family.program_operator(cfg, arrays)
        pc = _bind(template, {k: v[0] for k, v in state.items()}, op)
        return core.gmres(op, b, x0, precond=pc, **kw)

    return per_shard(solve, (specs, vec, vec, vec), result), (arrays, state)


def _solver_kw(cfg: dict, traffic: dict) -> dict:
    s = cfg["solver"]
    return dict(m=int(s["m"]), tol=float(s["tol"]),
                max_restarts=int(s["max_restarts"]), gs=traffic["gs"])


def _bind(template, state: dict, op):
    """The preconditioner built in set-up, with its arrays ``state`` and
    its operator ``op`` as the timed call holds them."""
    pc = copy.copy(template)
    vars(pc).update(state)
    if "op" in vars(pc):
        pc.op = op
    return pc


def closed_loop(ctx: "Context") -> List[tuple]:
    """Solves back to back; returns the sampled answers ``(x, b, tol)``,
    as the devices hold them."""
    import jax

    solve, args = make_solve(ctx.family, ctx.cfg, ctx.traffic, ctx.arrays,
                             ctx.mesh)
    rows = ctx.pool
    order = generator.pool_order(ctx.traffic, ctx.seed)
    chain = bool(ctx.traffic.get("chain"))

    def inputs(i, prev):
        """Right-hand side and first guess of solve ``i`` of the window;
        ``prev`` is the answer of the solve before, None for the warm-up.
        A time stepper's warm-up is its step 0 and solve ``i`` its step
        ``i + 1``; every ``CHAIN_STEPS`` steps it takes the first state
        again."""
        if chain:
            first = prev is None or (i + 1) % CHAIN_STEPS == 0
            b = rows[order[0]] if first else prev
            return b, b
        return rows[order[i % len(order)]], None

    warm = jax.block_until_ready(solve(*args, *inputs(0, None)))
    prev = warm.x
    ctx.setup_done()

    keep = int(ctx.traffic["check_sample"])
    pick = generator.rng(ctx.seed, "sample")
    sample: List[tuple] = []
    results = []
    ctx.open_window()
    t_start = _now()
    i = 0
    traced = 0
    while True:
        b, x0 = inputs(i, prev)
        with ctx.span("bench.solve"):
            t0 = _now()
            res = jax.block_until_ready(solve(*args, b, x0))
            t1 = _now()
        prev = res.x
        results.append((t0, t1, res.inner_steps, res.restarts,
                        res.converged))
        slot = i if i < keep else int(pick.integers(0, i + 1))
        if slot < keep:
            item = (res.x, b)
            if slot < len(sample):
                sample[slot] = item
            else:
                sample.append(item)
        i += 1
        if ctx.tracing and t1 - t_start >= ctx.trace_s:
            ctx.stop_trace()
            traced = i
        if t1 - t_start >= ctx.seconds:
            break
    if ctx.tracing:
        traced = i
    ctx.close_window()
    ctx.run.window_s = results[-1][1] - t_start
    counts = jax.device_get([r[2:] for r in results])
    ctx.run.solves = [
        {"t0": r[0] - t_start, "t1": r[1] - t_start,
         "inner_steps": int(c[0]), "restarts": int(c[1]),
         "converged": bool(c[2]), "traced": k < traced}
        for k, (r, c) in enumerate(zip(results, counts))]
    tol = float(ctx.cfg["solver"]["tol"])
    return [(x, b, tol) for x, b in sample]


def open_loop(ctx: "Context") -> List[tuple]:
    """Requests on a schedule through the program's ``SolverServer``."""
    import jax

    from repro.serve import SolverServer
    from repro.serve.request import DONE, REJECTED

    cfg, traffic = ctx.cfg, ctx.traffic
    op = ctx.family.program_operator(cfg, ctx.arrays)
    kw = dict(m=int(cfg["solver"]["m"]), **cfg["server"])
    if "gs" in traffic:
        kw["gs"] = traffic["gs"]
    srv = SolverServer(op, **kw)
    cycles: List[Any] = []
    program_cycle = srv.handle.cycle

    def cycle(*a, **k):
        out = program_cycle(*a, **k)
        cycles.append(out[2])
        return out

    srv.handle.cycle = cycle
    rows = [np.asarray(r) for r in jax.device_get(ctx.pool)]
    max_restarts = int(cfg["solver"]["max_restarts"])
    lo, hi = traffic["tol_loguniform"]
    warm = np.geomspace(float(lo), float(hi), 2 * srv.handle.k)
    for i, tol in enumerate(warm):
        srv.submit(rows[i % len(rows)], tol=float(tol),
                   max_restarts=max_restarts)
    srv.run()
    ctx.setup_done()

    sched = generator.open_schedule(traffic, ctx.seed, ctx.seconds)
    n_req = len(sched.due)
    rid_of: Dict[int, int] = {}
    done_at = np.full(n_req, np.nan)
    status: List[Optional[str]] = [None] * n_req
    ticks = []
    c_first = len(cycles)
    c_traced = None
    closed = False
    ctx.open_window()
    t_start = _now()
    nxt = 0
    finished = 0
    while True:
        now = _now() - t_start
        if ctx.tracing and now >= min(ctx.trace_s, ctx.seconds):
            ctx.stop_trace()
            c_traced = len(cycles)
        if not closed and now >= ctx.seconds:
            ctx.close_window()
            closed = True
        if now >= ctx.seconds + DRAIN_S:
            break
        if nxt < n_req and sched.due[nxt] <= now:
            with ctx.span("bench.submit"):
                while nxt < n_req and sched.due[nxt] <= now:
                    rid = srv.submit(rows[sched.rhs[nxt]],
                                     tol=float(sched.tol[nxt]),
                                     max_restarts=max_restarts)
                    rid_of[rid] = nxt
                    out = srv.results.get(rid)
                    if out is not None and out.status == REJECTED:
                        status[nxt] = REJECTED
                        finished += 1
                    nxt += 1
        if srv.state.busy or srv.ingress.peek() is not None:
            n_cycles = len(cycles)
            with ctx.span("bench.tick"):
                t0 = _now()
                retired = srv.step()
                t1 = _now()
            ticks.append({"t0": t0 - t_start, "t1": t1 - t_start,
                          "cycle": len(cycles) > n_cycles})
            for r in retired:
                j = rid_of.get(r.req.rid)
                if j is not None and status[j] is None:
                    status[j] = r.status
                    done_at[j] = t1 - t_start
                    finished += 1
        elif nxt < n_req:
            with ctx.span("bench.wait"):
                _sleep(max(0.0, min(sched.due[nxt] - now,
                                        max(ctx.seconds - now, 0.0))))
        if finished == n_req and nxt == n_req:
            if not closed:
                # Everything due was served before the window closed:
                # idle until it does, so the window is always ``seconds``.
                with ctx.span("bench.wait"):
                    _sleep(max(0.0, ctx.seconds - now))
                continue
            break
    if c_traced is None:
        c_traced = len(cycles)
    ctx.close_window()
    end = _now() - t_start
    ctx.run.window_s = float(ctx.seconds)
    ctx.run.ticks = ticks
    ctx.run.traced_cycles = [
        [int(v) for v in np.asarray(c)] for c in cycles[c_first:c_traced]]
    answers = []
    for rid, j in rid_of.items():
        ctx.run.requests.append({
            "due": float(sched.due[j]),
            "done": None if math.isnan(done_at[j]) else float(done_at[j]),
            "status": status[j] or "unfinished",
            "wait": float(done_at[j] - sched.due[j]),
            "late": float(end - sched.due[j])})
        if status[j] == DONE:
            answers.append((j, rid))
    ctx.run.requests.sort(key=lambda r: r["due"])
    keep = int(traffic["check_sample"])
    pick = generator.rng(ctx.seed, "sample")
    chosen = sorted(answers)
    if len(chosen) > keep:
        idx = pick.choice(len(chosen), size=keep, replace=False)
        chosen = [chosen[i] for i in sorted(idx)]
    sample = [(srv.results[rid].x, rows[sched.rhs[j]], float(sched.tol[j]))
              for j, rid in chosen]
    del srv
    return sample


LOOPS: Dict[str, Callable] = {"closed": closed_loop, "open": open_loop}


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------
class Context:
    """The state of one run, shared by the loop and the harness."""

    def __init__(self, layout: Layout, cell: dict, seed: int,
                 seconds: float, traced: bool, t_process: float,
                 counter: Optional[CompileCounter]):
        self.layout = layout
        self.cell = cell
        self.cfg = layout.config(cell)
        self.traffic = layout.traffic(cell)
        self.family = layout.family(self.cfg)
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.traced = traced
        self.t_process = t_process
        self.counter = counter
        self.trace_dir: Optional[str] = None
        self.trace_s = TRACE_S
        self.tracing = False
        self.arrays: dict = {}
        self.pool: list = []
        self.mesh = None
        self.run: Optional[Run] = None

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def setup_done(self) -> None:
        """End set-up.  What set-up allocated is collected and frozen, so
        that a collection inside the window scans only the window's own
        objects and not the hundreds of thousands JAX's start-up made (a
        stall that read as an 851 ms p95 at 128 requests/s on a TPU v5e)."""
        gc.collect()
        gc.freeze()
        self.run.setup_s = _now() - self.t_process

    def open_window(self) -> None:
        """Open the measured window; in a traced run, start the profiler
        and the window's span, which cover its first ``TRACE_S`` seconds."""
        import jax

        if self.counter is not None:
            self.counter.open = True
        if self.traced:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.trace_dir)
            self._span = self.span("bench.window")
            self._span.__enter__()
            self.tracing = True

    def stop_trace(self) -> None:
        import jax

        if self.tracing:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.tracing = False

    def close_window(self) -> None:
        self.stop_trace()
        if self.counter is not None:
            self.counter.open = False


def build_system(ctx: Context) -> None:
    """The family's arrays and right-hand-side pool, made on the device
    from the seed; for a cell on several chips, placed over its mesh."""
    import jax

    chips = int(ctx.cell["chips"])
    if chips > 1:
        ctx.mesh = sharded_mesh(ctx.family, ctx.cfg, ctx.traffic, chips)
    key = jax.random.PRNGKey(generator.key_int(ctx.seed, "system"))
    ctx.arrays, pool = ctx.family.build(ctx.cfg, key,
                                        int(ctx.traffic["pool"]))
    ctx.pool = list(pool)
    if ctx.mesh is not None:
        ctx.arrays, ctx.pool = place(ctx.family, ctx.cfg, ctx.mesh,
                                     ctx.arrays, ctx.pool)


def run_cell(root, workload: str, seed: int, seconds: float, traced: bool,
             *, t_process: float, require_tpu: bool = True,
             counter: Optional[CompileCounter] = None) -> dict:
    """Run one cell once and return its result object."""
    layout = Layout(root)
    cell = layout.cell(workload)
    chips = int(cell["chips"])
    device = device_info(chips, require_tpu)
    ctx = Context(layout, cell, seed, seconds, traced, t_process, counter)
    cfg, traffic = ctx.cfg, ctx.traffic
    build_system(ctx)
    peak_table = roofline.peaks(device["kind"]) if require_tpu else None
    ctx.run = Run(cell=cell, config=cfg, traffic=traffic, seed=int(seed),
                  seconds=float(seconds),
                  shapes=ctx.family.shapes(cfg, ctx.arrays),
                  peaks=peak_table)
    sample = LOOPS[traffic["loop"]](ctx)
    run = ctx.run
    device["memory_peak_bytes"] = memory_peak(chips)
    if traced:
        try:
            run.trace = trace_mod.Summary(trace_mod.read_xplane(ctx.trace_dir))
        finally:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s

    host = ctx.family.HostSystem(cfg, ctx.arrays)
    ctx.arrays, ctx.pool = {}, []
    checks = check_answers(host, sample, cfg)
    attempted, failed = outcome_counts(run)
    checks["unfinished"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    for m in layout.metrics(cell, traced):
        value = layout.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = run.trace.breakdown()
    if counter is not None:
        result["window_compiles"] = counter.count
    result["checks"] = checks
    return result


def check_answers(host, sample: List[tuple], cfg: dict) -> dict:
    """The widest ratio of a sampled answer's float64 relative residual to
    its tolerance, beside the configuration's limit.

    The family's ``HostSystem.residuals`` reads the answers and right-hand
    sides once, in order, and each is copied to the host (gathered from
    its shards) only when it is read: a stencil's residuals take one
    answer at a time, so the host holds one answer, not the sample."""
    limit = float(cfg["limits"]["resid_over_tol"])
    if not sample:
        return {"resid_over_tol_max": {"value": math.inf, "limit": limit}}
    xs = (np.asarray(x, np.float32) for x, _, _ in sample)
    bs = (np.asarray(b, np.float32) for _, b, _ in sample)
    tols = np.asarray([t for _, _, t in sample])
    ratios = host.residuals(xs, bs) / tols
    return {"resid_over_tol_max": {"value": float(np.max(ratios)),
                                   "limit": limit}}


def outcome_counts(run: Run):
    """Attempted and failed: solves that did not converge, or requests
    due in the window that did not come back DONE."""
    if run.requests:
        failed = sum(r["status"] != "done" for r in run.requests)
        return len(run.requests), failed
    failed = sum(not s["converged"] for s in run.solves)
    return len(run.solves), failed
