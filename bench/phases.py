#!/usr/bin/env python3
"""Where a cell's time goes, by the program's own spans and named scopes.

    python3 bench/phases.py --workload dense10k.serve --seed 7 --seconds 8 \
        [--excerpt trace_serve_tick.json]

One traced run of the cell as ``run.py --trace 1`` makes it (the first
``harness.TRACE_S`` seconds of the window traced), then one JSON line:

  per_solve_ms    device milliseconds per traced solve (or time step)
                  under each scope of the solver: ``operator_ms.<cell>``,
                  ``orthogonalize_ms.<cell>``, ...; ``operator`` leaves
                  out what runs inside ``arnoldi_step``
  scoped_share    the share of the window's op time under a named scope
  by_scope        device seconds per scope path; ``ops_by_scope`` the
                  same for each of the ten ops with the most time
  idle_gaps       idle device time by the innermost host span covering
                  it, the benchmark's (``bench.*``) or the program's
                  (``repro.*``)
  serve cells     ``tick_host_ms.<cell>`` (a cycle tick's step span
                  minus its sync), ``tick_sync_ms.<cell>``, the mean
                  ``bench.tick`` span around those ticks, the mean of
                  each phase, ``queue_ms_p50.<cell>`` (nearest rank over
                  the requests due in the window of
                  ``SolveOutcome.queue_s``; one never placed counts at
                  what it had waited when the run ended) and the server's
                  ``cycle_traces``

An op's scope comes from the ``op_name`` metadata of the module that ran
it (``scopes``): the script compiles the cell's solve, or the server's
cycle, once more in its own process and reads the compiled text.  The
answers are not checked here; ``run.py`` checks them.  ``--excerpt``
writes the first cycle ticks of a serve cell's trace, with their spans,
modules and op names, for the tests.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

EXCERPT_TICKS = 1
EXCERPT_NAME = 64          # characters of an op's name kept


def _traced_run(root, workload: str, seed: int, seconds: float,
                require_tpu: bool):
    """Run the cell's loop with the profiler on; returns the context and
    the program objects the loop made (its jitted solve, or its server,
    and the first rid of the window)."""
    import repro.serve
    from bench import harness

    kept = {}
    layout = harness.Layout(root)
    cell = layout.cell(workload)
    harness.device_info(int(cell["chips"]), require_tpu)

    class Ctx(harness.Context):
        def setup_done(self):
            super().setup_done()
            if "server" in kept:
                kept["first_rid"] = len(kept["server"].results)

    class KeptServer(repro.serve.SolverServer):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            kept["server"] = self

    make_solve = harness.make_solve

    def keep_solve(*args, **kwargs):
        kept["solve"] = out = make_solve(*args, **kwargs)
        return out

    ctx = Ctx(layout, cell, seed, seconds, True, time.perf_counter(), None)
    harness.build_system(ctx)
    ctx.run = harness.Run(cell=cell, config=ctx.cfg, traffic=ctx.traffic,
                          seed=seed, seconds=seconds,
                          shapes=ctx.family.shapes(ctx.cfg, ctx.arrays),
                          peaks=None)
    with mock.patch.object(harness, "make_solve", keep_solve), \
            mock.patch.object(repro.serve, "SolverServer", KeptServer):
        harness.LOOPS[ctx.traffic["loop"]](ctx)
    return ctx, kept


def _compiled_texts(ctx, kept) -> list:
    """The compiled text of each program the loop ran."""
    if "server" in kept:
        return [kept["server"].handle.lower().compile().as_text()]
    solve, args = kept["solve"]
    b = ctx.pool[0]
    x0 = b if ctx.traffic.get("chain") else None
    return [solve.lower(*args, b, x0).compile().as_text()]


def _queue_s(ctx, kept) -> list:
    """Each window request's queue seconds, in due order."""
    srv, first = kept["server"], kept["first_rid"]
    out = []
    for j, req in enumerate(ctx.run.requests):
        got = srv.results.get(first + j)
        q = None if got is None else got.queue_s
        out.append(req["late"] if q is None else q)
    return out


def profile(root, workload: str, seed: int, seconds: float,
            require_tpu: bool = True, excerpt=None) -> dict:
    from bench import scopes, trace

    ctx, kept = _traced_run(root, workload, seed, seconds, require_tpu)
    try:
        raw = trace.read_xplane(ctx.trace_dir)
        prog = scopes.read_program(ctx.trace_dir)
    finally:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    summary = trace.Summary(raw)
    texts = _compiled_texts(ctx, kept)
    names = {scopes.module_of(t): scopes.op_names(t) for t in texts}
    scoped = scopes.scoped_ops(summary.ops, prog["modules"], names)
    spans = [e for e in prog["spans"]
             if summary.start <= e[1] and e[1] + e[2] <= summary.end]
    host = summary.host + spans
    top = [n for n, _ in trace.top_ops(summary.all_ops())]
    cell = workload.rsplit(".", 1)[-1]
    out = {
        "workload": workload, "seed": seed,
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "scoped_share": scopes.scoped_share(scoped),
        "by_scope": scopes.by_scope(scoped),
        "ops_by_scope": {
            n: scopes.by_scope(scopes.scoped_ops(
                {p: [e for e in evs if trace.base_name(e[0]) == n]
                 for p, evs in summary.ops.items()},
                prog["modules"], names))
            for n in top},
        "idle_gaps": trace.idle_gaps(summary.all_ops(), host, summary.start,
                                     summary.end),
    }
    traced = sum(s["traced"] for s in ctx.run.solves)
    if traced:
        out["per_solve_ms"] = {
            f"{s}_ms.{cell}": scopes.scope_s(
                scoped, s, ("arnoldi_step",) if s == "operator" else ())
            / traced * 1e3
            for s in scopes.SCOPES}
    if "server" in kept:
        out.update({
            f"tick_host_ms.{cell}": scopes.tick_host_ms(host),
            f"tick_sync_ms.{cell}": scopes.tick_sync_ms(host),
            "bench_tick_ms": scopes.tick_span_ms(host),
            "tick_phases_ms": scopes.tick_phases_ms(host),
            f"queue_ms_p50.{cell}": scopes.nearest_rank(
                _queue_s(ctx, kept), 0.5) * 1e3,
            "cycle_traces": kept["server"].metrics()["cycle_traces"]})
        if excerpt is not None:
            _write_excerpt(excerpt, raw, prog, host, names, ctx)
    return out


def _write_excerpt(path, raw, prog, host, names, ctx) -> None:
    """The first ``EXCERPT_TICKS`` cycle ticks of the traced window."""
    from bench import scopes

    ticks = [e for e in host if e[0] == scopes.TICK]
    steps = [s for s, _ in scopes.cycle_ticks(host)][:EXCERPT_TICKS]
    ticks = [t for t in ticks
             if any(t[1] <= s[1] <= t[1] + t[2] for s in steps)]
    a, b = ticks[0][1], ticks[-1][1] + ticks[-1][2]

    def within(evs):
        return [[n[:EXCERPT_NAME], round(s), round(d)] for n, s, d in evs
                if a <= s and s + d <= b]

    devices = {p: within(evs) for p, evs in raw["devices"].items()}
    used = {scopes.instruction(n) for evs in devices.values()
            for n, _, _ in evs}
    doc = {
        "recorded": (f"{ctx.cell['name']} seed {ctx.seed}, the first "
                     f"{EXCERPT_TICKS} cycle ticks of a traced window; "
                     "bench.window set to them; op names cut at "
                     f"{EXCERPT_NAME} characters"),
        "devices": devices,
        "host": [["bench.window", round(a), round(b - a)]] + within(host),
        "modules": {p: [[n, round(s), round(d)] for n, s, d in evs
                        if s < b and a < s + d]
                    for p, evs in prog["modules"].items()},
        "op_names": {m: {i: o for i, o in t.items() if i in used}
                     for m, t in names.items()},
        "traced_cycles": ctx.run.traced_cycles[:len(ticks)],
        "n": int(ctx.run.shapes["n"]),
    }
    pathlib.Path(path).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(path).write_text(json.dumps(doc))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--excerpt", default=None)
    args = ap.parse_args(argv)
    from repro.runtime import compile_cache

    compile_cache.configure()
    out = profile(ROOT, args.workload, args.seed, args.seconds,
                  excerpt=args.excerpt)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
