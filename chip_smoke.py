#!/usr/bin/env python3
"""Smoke test: the solver's main path on TPU, through its public entry points.

    python chip_smoke.py             # one chip: phases dense, stencil, serve
    python chip_smoke.py --chips 4   # four chips: the row-sharded stencil
                                     # solve against the one-chip solve

Phases (one chip):
  dense    the paper's largest system: random diagonally dominant
           N = 10,000 f32, ``DenseOperator(backend="pallas")``,
           ``gmres(m=30, tol=1e-6)`` with ``gs="fused"`` and with
           ``gs="cgs2_pipelined"``; checked against a float64
           ``numpy.linalg.solve`` of the same system on the host.
  stencil  2-D Poisson 1024 x 1024 (n = 1,048,576), banded Pallas SpMV,
           ``gmres(m=30, max_restarts=5, gs="cgs2_pipelined")`` with a
           banded ILU(0) preconditioner (set up inside the same program);
           checked against the same solve traced in kernel mode "ref"
           (XLA only) and against |b - A x| recomputed in float64.
  serve    ``SolverServer(op, m=30, k=8)`` over a dense N = 8,192 system,
           16 requests with tolerances 1e-4 ... 1e-6, drained by ``run()``;
           every outcome DONE with true residual within 2x its tol.

Each phase prints one line: its name, its wall time (one cold run,
compilation included — not a benchmark), the ``tpu_custom_call`` count of
its compiled program, the Pallas kernels in it, and its check.  A phase
whose program holds no kernel fails: a fits gate then sent the whole phase
to the reference.  Any failure raises and the script exits non-zero.

The last line of standard output is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
after every phase passed.  Without a TPU, or with ``REPRO_KERNELS`` set,
the script exits non-zero before any phase and prints no result.  JAX's
persistent compilation cache is placed by ``repro.runtime.compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# The autotune cache stays in memory: a tile recorded by another run must
# not change what this one compiles.
os.environ.setdefault("REPRO_TUNE_CACHE", "off")

_KERNEL_RE = re.compile(
    r"%([A-Za-z_][\w-]*?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"")


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def _kernels_in(hlo_text: str) -> list:
    """Names of the Pallas kernels (one per tpu_custom_call) in a program."""
    return _KERNEL_RE.findall(hlo_text)


def _compile(fn, *args, require_kernels: bool = True):
    """Lower and compile ``jit(fn)`` for ``args``; returns (compiled,
    kernel names, compile seconds)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    kernels = _kernels_in(compiled.as_text())
    if require_kernels and not kernels:
        raise SmokeFailure("compiled program holds no tpu_custom_call: a fits "
                           "gate sent the whole phase to the reference")
    return compiled, kernels, secs


def _timed(compiled, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def _report(name: str, wall: float, kernels: list, check: dict, ok: bool,
            **extra) -> None:
    line = {"phase": name, "wall_s_cold": wall,
            "tpu_custom_call": len(kernels),
            "kernels": sorted(set(kernels)), "check": check,
            "passed": ok, **extra}
    print(json.dumps(line), flush=True)
    if not ok:
        raise SmokeFailure(f"phase {name}: check failed: {check}")


def _rel(a, b) -> float:
    import numpy as np

    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def phase_dense(n: int = 10_000, m: int = 30, tol: float = 1e-6) -> None:
    import jax
    import numpy as np

    from repro.core import gmres, operators

    a = operators.random_diagdom(jax.random.PRNGKey(0), n)
    b = jax.random.normal(jax.random.PRNGKey(1), (n,), a.dtype)
    t0 = time.perf_counter()
    a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
    x_ref = np.linalg.solve(a64, b64)
    ref_s = time.perf_counter() - t0
    for gs in ("fused", "cgs2_pipelined"):
        t0 = time.perf_counter()
        compiled, kernels, comp_s = _compile(
            lambda a, b, gs=gs: gmres(operators.DenseOperator(a, "pallas"),
                                      b, m=m, tol=tol, gs=gs), a, b)
        res, run_s = _timed(compiled, a, b)
        wall = time.perf_counter() - t0
        x = np.asarray(res.x, np.float64)
        err = _rel(x, x_ref)
        resid = float(np.linalg.norm(b64 - a64 @ x) / np.linalg.norm(b64))
        _report(f"dense gs={gs}", wall, kernels,
                {"rel_err_x_vs_f64_solve": err, "limit_err": 1e-4,
                 "true_rel_residual": resid, "limit_residual": 2 * tol},
                err <= 1e-4 and resid <= 2 * tol,
                n=n, compile_s=comp_s, run_s=run_s,
                restarts=int(res.restarts), host_f64_solve_s=ref_s)


def _banded_apply_f64(bands, offsets, x):
    """y = A x in float64 on the host for a DIA band stack."""
    import numpy as np

    n = x.shape[0]
    y = np.zeros(n)
    for d, off in enumerate(offsets):
        lo, hi = max(0, -off), min(n, n - off)
        y[lo:hi] += bands[d, lo:hi] * x[lo + off:hi + off]
    return y


def phase_stencil(nx: int = 1024, m: int = 30, max_restarts: int = 5) -> None:
    import jax
    import numpy as np

    from repro.core import gmres, preconditioners, stencils
    from repro.core.operators import BandedOperator
    from repro.kernels import tuning

    op = stencils.poisson_2d(nx, fmt="banded", backend="pallas")
    offsets = op.offsets
    b = jax.random.normal(jax.random.PRNGKey(2), (nx * nx,), op.bands.dtype)

    def solver():
        # A fresh function per trace: jit caches traces by function, and
        # the kernel mode is read at trace time.
        def solve(bands, b):
            a = BandedOperator(bands, offsets, "pallas")
            pc = preconditioners.make_preconditioner("banded_ilu0", a)
            return gmres(a, b, m=m, max_restarts=max_restarts,
                         gs="cgs2_pipelined", precond=pc)
        return solve

    t0 = time.perf_counter()
    compiled, kernels, comp_s = _compile(solver(), op.bands, b)
    res, run_s = _timed(compiled, op.bands, b)
    wall = time.perf_counter() - t0
    with tuning.force_kernel_mode("ref"):
        t1 = time.perf_counter()
        ref_prog, ref_kernels, _ = _compile(solver(), op.bands, b,
                                            require_kernels=False)
        ref, _ = _timed(ref_prog, op.bands, b)
        ref_s = time.perf_counter() - t1
    x = np.asarray(res.x, np.float64)
    x_ref = np.asarray(ref.x, np.float64)
    b64 = np.asarray(b, np.float64)
    r_true = float(np.linalg.norm(
        b64 - _banded_apply_f64(np.asarray(op.bands, np.float64), offsets,
                                x)))
    r_told = float(res.residual)
    err = _rel(x, x_ref)
    resid_gap = abs(r_told - r_true) / r_true
    _report("stencil", wall, kernels,
            {"rel_diff_x_vs_ref_mode": err, "limit_diff": 1e-3,
             "reported_residual": r_told, "recomputed_residual_f64": r_true,
             "residual_rel_gap": resid_gap, "limit_gap": 1e-3,
             "ref_mode_kernels": len(ref_kernels)},
            err <= 1e-3 and resid_gap <= 1e-3 and not ref_kernels,
            n=nx * nx, compile_s=comp_s, run_s=run_s,
            restarts=int(res.restarts),
            rel_residual=r_true / float(np.linalg.norm(b64)),
            ref_mode_wall_s_cold=ref_s)


def phase_serve(n: int = 8192, k: int = 8, m: int = 30,
                requests: int = 16) -> None:
    import jax
    import numpy as np

    from repro.core import operators
    from repro.serve import SolverServer
    from repro.serve.request import DONE

    a = operators.random_diagdom(jax.random.PRNGKey(3), n)
    op = operators.DenseOperator(a, "pallas")
    bs = jax.random.normal(jax.random.PRNGKey(4), (requests, n), a.dtype)
    tols = np.geomspace(1e-4, 1e-6, requests)
    t0 = time.perf_counter()
    srv = SolverServer(op, m=m, k=k)
    rids = [srv.submit(bs[i], tol=float(tols[i])) for i in range(requests)]
    ticks = srv.run()
    wall = time.perf_counter() - t0
    kernels = _kernels_in(srv.handle.lower().compile().as_text())
    if not kernels:
        raise SmokeFailure("serve cycle holds no tpu_custom_call")
    a64, bs64 = np.asarray(a, np.float64), np.asarray(bs, np.float64)
    statuses, ratios = [], []
    for i, rid in enumerate(rids):
        out = srv.results[rid]
        statuses.append(out.status)
        x = np.asarray(out.x, np.float64) if out.x is not None else None
        r = (np.inf if x is None else
             np.linalg.norm(bs64[i] - a64 @ x) / np.linalg.norm(bs64[i]))
        ratios.append(float(r / tols[i]))
    _report("serve", wall, kernels,
            {"all_done": all(s == DONE for s in statuses),
             "max_true_residual_over_tol": max(ratios), "limit": 2.0},
            all(s == DONE for s in statuses) and max(ratios) <= 2.0,
            n=n, k=k, requests=requests, ticks=ticks,
            cycles=srv.handle.cycles_run)


def phase_sharded(nx: int = 1024, chips: int = 4,
                  max_restarts: int = 5) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import make_mesh
    from repro.core import gmres, gmres_sharded, stencils
    from repro.core.operators import BandedOperator

    mesh = make_mesh((chips,), ("rows",))
    devs = list(mesh.devices.flat)
    if (len({d.id for d in devs}) != chips
            or any(d.platform != "tpu" for d in devs)):
        raise SmokeFailure(f"mesh holds {devs}, not {chips} distinct TPUs")
    op = stencils.poisson_2d(nx, fmt="banded", backend="pallas")
    offsets = op.offsets
    n = nx * nx
    b = jax.random.normal(jax.random.PRNGKey(5), (n,), op.bands.dtype)
    bands_s = jax.device_put(op.bands, NamedSharding(mesh, P(None, "rows")))
    b_s = jax.device_put(b, NamedSharding(mesh, P("rows")))

    t0 = time.perf_counter()
    compiled, kernels, comp_s = _compile(
        lambda bands, b: gmres_sharded(
            mesh, "rows", BandedOperator(bands, offsets, "pallas"), b,
            gs="cgs2_pipelined", max_restarts=max_restarts), bands_s, b_s)
    res, run_s = _timed(compiled, bands_s, b_s)
    wall = time.perf_counter() - t0

    shards = {}
    for name, arr, width in (("bands", bands_s, n // chips),
                             ("x", res.x, n // chips)):
        got = [(s.device.id, s.data.shape[-1]) for s in arr.addressable_shards]
        shards[name] = got
        if (len({d for d, _ in got}) != chips
                or any(w != width for _, w in got)):
            raise SmokeFailure(f"{name} is not one shard per device: {got}")

    t1 = time.perf_counter()
    one_prog, one_kernels, _ = _compile(
        lambda bands, b: gmres(BandedOperator(bands, offsets, "pallas"), b,
                               gs="cgs2_pipelined",
                               max_restarts=max_restarts), op.bands, b)
    one, _ = _timed(one_prog, op.bands, b)
    one_s = time.perf_counter() - t1
    err = _rel(np.asarray(res.x, np.float64), np.asarray(one.x, np.float64))
    _report(f"sharded x{chips}", wall, kernels,
            {"rel_diff_x_vs_one_chip": err, "limit": 1e-3,
             "shards_per_device": shards},
            err <= 1e-3, n=n, compile_s=comp_s, run_s=run_s,
            restarts=int(res.restarts), one_chip_restarts=int(one.restarts),
            one_chip_wall_s_cold=one_s,
            one_chip_kernels=sorted(set(one_kernels)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the row-sharded stencil solve and "
                         "its one-chip comparison")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_KERNELS"):
        print("chip_smoke: REPRO_KERNELS is set; the smoke test runs the "
              "kernels' own dispatch, unset it", file=sys.stderr)
        return 2
    import jax

    import repro
    from repro.kernels import tuning
    from repro.runtime import compile_cache

    src = os.path.join(HERE, "src") + os.sep
    if not os.path.abspath(repro.__file__).startswith(src):
        print(f"chip_smoke: repro was imported from {repro.__file__}, not "
              f"from this checkout's {src}", file=sys.stderr)
        return 1

    cache_dir = compile_cache.configure()
    devs = jax.devices()
    mode = tuning.kernel_mode()
    if devs[0].platform != "tpu" or mode != "compiled":
        print(f"chip_smoke: needs a TPU with compiled kernels; found "
              f"platform={devs[0].platform!r} kernel_mode={mode!r}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 1
    print(json.dumps({"devices": len(devs), "kind": devs[0].device_kind,
                      "kernel_mode": mode, "compile_cache": cache_dir,
                      "kernels_on_reference_on_chip":
                          list(tuning.GATHER_KERNELS)}), flush=True)
    if args.chips == 4:
        phase_sharded(chips=4)
    else:
        phase_dense()
        phase_stencil()
        phase_serve()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
