#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest rate the server sustains.

    python3 bench/knee.py --workload dense10k.serve --seed 7 --seconds 10 \
        --rates 200 300 400 500

One process, one system: for each rate the cell's traffic is offered at
that rate for ``seconds`` and one JSON line reports the requests due, the
share done inside the window, the p50 and p95 wait, and how the wait grew
from the first quarter of the window to the last.  Below the knee the
wait does not grow; above it the backlog grows all through the window.
The cell's traffic file then fixes its rate; the benchmark's runs never
search for one.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def sweep(root, workload, seed, seconds, rates, require_tpu=True):
    import jax
    import numpy as np

    from bench import generator, harness

    layout = harness.Layout(root)
    cell = layout.cell(workload)
    harness.device_info(int(cell["chips"]), require_tpu)
    ctx = harness.Context(layout, cell, seed, seconds, False,
                          time.perf_counter(), None)
    key = jax.random.PRNGKey(generator.key_int(seed, "system"))
    ctx.arrays, pool = ctx.family.build(ctx.cfg, key,
                                        int(ctx.traffic["pool"]))
    ctx.pool = list(pool)
    base = dict(ctx.traffic)
    for rate in rates:
        ctx.traffic = dict(base, rate=float(rate))
        ctx.run = harness.Run(cell=cell, config=ctx.cfg, traffic=ctx.traffic,
                              seed=seed, seconds=seconds, shapes={},
                              peaks=None)
        harness.open_loop(ctx)
        reqs = ctx.run.requests
        waits = np.array([r["wait"] if r["status"] == "done" else r["late"]
                          for r in reqs])
        due = np.array([r["due"] for r in reqs])
        q = seconds / 4
        first, last = waits[due < q], waits[due >= seconds - q]
        done_in = sum(r["status"] == "done" and r["done"] <= seconds
                      for r in reqs)
        yield {"rate": rate, "due": len(reqs),
               "done_in_window_share": done_in / max(len(reqs), 1),
               "solves_per_s": done_in / seconds,
               "wait_p50_ms": float(np.percentile(waits, 50)) * 1e3,
               "wait_p95_ms": float(np.sort(waits)[
                   int(np.ceil(0.95 * len(waits))) - 1]) * 1e3,
               "wait_growth": float(np.mean(last) / np.mean(first)),
               "tick_ms": float(np.mean([t["t1"] - t["t0"]
                                         for t in ctx.run.ticks
                                         if t["cycle"]])) * 1e3,
               "not_done": int(sum(r["status"] != "done" for r in reqs))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from repro.runtime import compile_cache

    compile_cache.configure()
    for line in sweep(ROOT, args.workload, args.seed, args.seconds,
                      args.rates):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
