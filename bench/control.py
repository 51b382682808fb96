#!/usr/bin/env python3
"""The control: the plain reference solve in the program's place, in the
precision below the configuration's, read by the benchmark's own check.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 [--count 8]

For each seed it builds the cell's system, takes the first ``count``
right-hand sides (and tolerances) the cell's traffic would send, solves
each with the configuration family's ``control_solve`` (a time stepper's
``count`` steps in turn, each from the answer before), and prints one
JSON line with the check's number beside its limit.  The check has to
refuse the control; the benchmark's own runs never run it.
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


def readings(root, workload: str, seed: int, count: int,
             require_tpu: bool = True) -> dict:
    import jax
    import numpy as np

    from bench import generator, harness

    layout = harness.Layout(root)
    cell = layout.cell(workload)
    harness.device_info(int(cell["chips"]), require_tpu)
    cfg, traffic = layout.config(cell), layout.traffic(cell)
    family = layout.family(cfg)
    key = jax.random.PRNGKey(generator.key_int(seed, "system"))
    arrays, pool = family.build(cfg, key, int(traffic["pool"]))
    if traffic["loop"] == "open":
        sched = generator.open_schedule(traffic, seed,
                                        float(layout.spec["run_seconds"]))
        picks = list(zip(sched.rhs[:count], sched.tol[:count]))
    else:
        tol = float(cfg["solver"]["tol"])
        picks = [(i, tol) for i in generator.pool_order(traffic, seed)[:count]]
    chain = bool(traffic.get("chain"))
    if chain:
        picks = [(picks[0][0], picks[0][1])] * count
    solve = jax.jit(lambda arrays, b, x0, tol: family.control_solve(
        cfg, arrays, b, tol, **({"x0": x0} if chain else {})))
    t0 = time.perf_counter()
    sample = []
    prev = None
    for i, tol in picks:
        b = pool[int(i)] if prev is None else prev
        x = jax.block_until_ready(solve(arrays, b, b if chain else None,
                                        np.float32(tol)))
        sample.append((np.asarray(x), np.asarray(b), float(tol)))
        if chain:
            prev = x
    secs = time.perf_counter() - t0
    host = family.HostSystem(cfg, arrays)
    check = harness.check_answers(host, sample, cfg)["resid_over_tol_max"]
    return {"workload": workload, "seed": seed, "control": True,
            "count": len(sample), "resid_over_tol_max": check["value"],
            "limit": check["limit"], "refused": check["value"] > check["limit"],
            "solve_s": secs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--count", type=int, default=8)
    args = ap.parse_args(argv)
    from repro.runtime import compile_cache

    compile_cache.configure()
    for seed in args.seeds:
        print(json.dumps(readings(ROOT, args.workload, seed, args.count)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
