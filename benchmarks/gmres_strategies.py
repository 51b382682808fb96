"""Paper Table 1 analogue: GMRES offload-strategy comparison over N.

The paper measured wall-time speedup of three R GPU packages vs
pracma::gmres on an NVIDIA 840M.  This container has no accelerator, so the
axis being measured shifts exactly the way DESIGN.md SS2 describes: the
strategies differ in WHERE the dispatch/fusion boundary sits —

    serial_numpy       per-op host dispatch      (pracma)
    offload_matvec     per-matvec device call + 2 boundary crossings (gmatrix)
    transfer_per_call  + full A re-transfer per call               (gputools)
    device_resident    ONE fused XLA program, zero boundary ops    (gpuR-vcl)

On CPU the "device" is XLA:cpu, so the measured speedup isolates the
dispatch/fusion effect the paper could not separate from raw GPU FLOPs.
The TPU projection of the same programs is in the roofline table.

All strategies solve the SAME diagonally-dominant dense system to the same
tolerance; correctness is asserted, matching solutions across strategies.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import strategies
from repro.core.operators import random_diagdom

SIZES_QUICK = (1_000, 2_000, 4_000)
SIZES_FULL = (1_000, 2_000, 3_000, 4_000, 5_000, 6_000, 7_000, 8_000,
              9_000, 10_000)


def _time(fn, *args, repeats=3, **kw):
    best = np.inf
    result = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        result = fn(*args, **kw)
        jax.block_until_ready(getattr(result, "x", result))
        best = min(best, time.perf_counter() - t0)
    return best, result


def run(full: bool = False, m: int = 30, tol: float = 1e-5):
    sizes = SIZES_FULL if full else SIZES_QUICK
    rows = []
    for n in sizes:
        a = np.asarray(random_diagdom(jax.random.PRNGKey(0), n), np.float32)
        b = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (n,)),
                       np.float32)
        t_serial, (x_ref, beta, *_rest) = _time(
            strategies.serial_numpy, a, b, m=m, tol=tol, repeats=2)
        assert beta / np.linalg.norm(b) < 10 * tol
        row = {"N": n, "serial_numpy_s": t_serial}
        for name in ("offload_matvec", "transfer_per_call"):
            t, (x, *_r) = _time(strategies.STRATEGIES[name], a, b, m=m,
                                tol=tol, repeats=2)
            np.testing.assert_allclose(x, x_ref, rtol=2e-2, atol=1e-3)
            row[f"{name}_x"] = t_serial / t
        # device_resident: exclude compile (steady-state, like the paper's
        # warm GPU timings), include execution only
        solve = lambda: strategies.device_resident(a, b, m=m, tol=tol)
        solve()                                    # compile warmup
        t, res = _time(lambda: solve(), repeats=3)
        np.testing.assert_allclose(np.asarray(res.x), x_ref, rtol=2e-2,
                                   atol=1e-3)
        row["device_resident_x"] = t_serial / t
        rows.append(row)
    return rows


def main(full: bool = False):
    from repro.runtime import compile_cache
    compile_cache.configure()
    rows = run(full=full)
    print("name,us_per_call,derived")
    for r in rows:
        base_us = r["serial_numpy_s"] * 1e6
        print(f"gmres_serial_N{r['N']},{base_us:.0f},speedup=1.00")
        for k in ("offload_matvec", "transfer_per_call", "device_resident"):
            sp = r[f"{k}_x"]
            print(f"gmres_{k}_N{r['N']},{base_us / sp:.0f},speedup={sp:.2f}")
    return rows


if __name__ == "__main__":
    import sys
    main(full="--full" in sys.argv)
