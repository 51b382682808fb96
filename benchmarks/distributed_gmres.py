"""Distributed GMRES scaling: the sharding study the paper's 2 GB wall

motivates.  Runs the row-sharded solver on 8 fake host devices (subprocess,
so the main process keeps its 1-device view) and reports:

  - wall time vs the single-device solver,
  - collective op counts/bytes from the lowered HLO (the real scaling
    quantity: per Arnoldi step CGS2 needs exactly 1 all-gather + 2 psums
    vs MGS's j+1 collective rounds; the banded kernel-path rows swap the
    all-gather for an O(halo) neighbor exchange, and the sharded s-step
    solver drops to ~4 rounds per s steps).

Everything drives the UNIFIED solver path — ``gmres_sharded`` /
``gmres_sstep_sharded`` are thin shard_map wrappers over the same cycle
the single-device rows run; there is no standalone local cycle here.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_CODE = textwrap.dedent("""
    import json, time
    import jax, jax.numpy as jnp
    from repro.runtime import compile_cache
    compile_cache.configure()
    from repro.core import (gmres, gmres_sharded, gmres_sstep,
                            gmres_sstep_sharded, operators, stencils)
    from repro.compat import make_mesh
    from repro.roofline import (parse_collectives,
                                innermost_loop_collectives)

    def coll_stats(jsol, *args):
        # Whole-program counts AND the innermost while-body counts: the
        # latter is the per-Arnoldi-step collective schedule (whole-program
        # counts dilute it with prologue/epilogue collectives).
        hlo = jsol.lower(*args).compile().as_text()
        colls = parse_collectives(hlo)
        nops = sum(c.count for c in colls)
        cbytes = sum(c.result_bytes * c.count for c in colls)
        _, loop = innermost_loop_collectives(hlo)
        loop_ops = sum(c.count for c in loop)
        loop_psums = sum(c.count for c in loop if c.kind == "all-reduce")
        return nops, cbytes, loop_ops, loop_psums

    def timed(jsol, *args):
        r = jsol(*args); r.x.block_until_ready()
        t0 = time.perf_counter(); r = jsol(*args); r.x.block_until_ready()
        return r, time.perf_counter() - t0

    def row(n, gs, t_single, t, r, stats):
        nops, cbytes, loop_ops, loop_psums = stats
        return {"n": n, "gs": gs, "t_single_us": t_single * 1e6,
                "t_sharded_us": t * 1e6, "steps": int(r.inner_steps),
                "restarts": int(r.restarts), "collective_ops": nops,
                "collective_bytes": cbytes, "loop_coll_ops": loop_ops,
                "loop_psums": loop_psums}

    out = []
    mesh = make_mesh((8,), ('model',))
    for n in (2048, 8192):
        a = operators.random_diagdom(jax.random.PRNGKey(0), n)
        b = jax.random.normal(jax.random.PRNGKey(1), (n,))

        single = jax.jit(lambda a, b: gmres(a, b, m=20, tol=1e-5, gs='cgs2'))
        _, t_single = timed(single, a, b)

        # s-step (communication-avoiding), single-device wall time; its
        # value is the ROUND count: (s + 4)/s rounds per step vs 4 (CGS2).
        # steps = one full m=20 cycle (residual checks are per-cycle).
        # Collective counts are PARSED from the lowered HLO like every
        # other row (a local program honestly counts 0) — no placeholder.
        ssol = jax.jit(lambda a, b: gmres_sstep(a, b, s=4, blocks=5,
                                                tol=1e-5))
        stats = coll_stats(ssol, a, b)
        r, t = timed(ssol, a, b)
        out.append(row(n, "SINGLEDEV_sstep4", t_single, t, r, stats))

        for gs, pc in (('cgs2', None), ('mgs', None),
                       ('cgs2', 'block_jacobi'),
                       ('cgs2_pipelined', None)):
            sol = lambda a, b, gs=gs, pc=pc: gmres_sharded(
                mesh, 'model', a, b, m=20, tol=1e-5, gs=gs, precond=pc)
            jsol = jax.jit(sol)
            stats = coll_stats(jsol, a, b)
            r, t = timed(jsol, a, b)
            out.append(row(n, gs + ("+bj" if pc else ""), t_single, t, r,
                           stats))

    # --- the shard-aware KERNEL path: banded stencil operators ----------
    # halo exchange instead of all-gather per matvec (watch
    # collective_bytes collapse vs the dense rows above), split-phase
    # CGS2 structure, the pipelined single-reduce scheme (1 psum per
    # step), and the CA s-step solver at ~4 rounds per s steps.
    # Restart budgets are capped: the interesting quantities (per-step
    # collective schedule, wall time per step) don't need full Poisson
    # convergence, which is slow unpreconditioned.
    for nx in (32, 64):
        n = nx * nx
        op = stencils.poisson_2d(nx, nx)       # jnp backend: the halo REF
        bb = jnp.sin(jnp.arange(n) * 0.37)     # path; kernels are bench-
        single = jax.jit(lambda o, v: gmres(   # marked in kernel_bench
            o, v, m=20, tol=1e-4, max_restarts=40, gs='cgs2'))
        _, t_single = timed(single, op, bb)
        for tag, sol in (
            ('banded_cgs2', lambda o, v: gmres_sharded(
                mesh, 'model', o, v, m=20, tol=1e-4, max_restarts=40,
                gs='cgs2')),
            ('banded_pipelined', lambda o, v: gmres_sharded(
                mesh, 'model', o, v, m=20, tol=1e-4, max_restarts=40,
                gs='cgs2_pipelined')),
            ('banded_sstep4', lambda o, v: gmres_sstep_sharded(
                mesh, 'model', o, v, s=4, blocks=5, tol=1e-4,
                max_restarts=40)),
            ('banded_sstep4_pipelined', lambda o, v: gmres_sstep_sharded(
                mesh, 'model', o, v, s=4, blocks=5, tol=1e-4,
                max_restarts=40, gs='cgs2_pipelined')),
        ):
            jsol = jax.jit(sol)
            stats = coll_stats(jsol, op, bb)
            r, t = timed(jsol, op, bb)
            out.append(row(n, tag, t_single, t, r, stats))
    print(json.dumps(out))
""")


def main():
    env = dict(os.environ)
    # Eight fake CPU devices: the child never touches the chip, which
    # stays with whichever process owns it.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    res = subprocess.run([sys.executable, "-c", _CODE], env=env,
                         capture_output=True, text=True, timeout=1800)
    if res.returncode != 0:
        raise RuntimeError(f"distributed_gmres child failed "
                           f"(rc={res.returncode}): {res.stderr[-2000:]}")
    rows = json.loads(res.stdout.strip().splitlines()[-1])
    print("name,us_per_call,derived")
    for r in rows:
        tag = (f"gmres_{r['gs'].replace('SINGLEDEV_', '')}_n{r['n']}"
               if r["gs"].startswith("SINGLEDEV_")
               else f"gmres_sharded8_{r['gs']}_n{r['n']}")
        print(f"{tag},{r['t_sharded_us']:.0f},"
              f"single_dev_us={r['t_single_us']:.0f};steps={r['steps']};"
              f"coll_ops={r['collective_ops']};"
              f"coll_bytes={r['collective_bytes']};"
              f"loop_coll_ops={r['loop_coll_ops']};"
              f"loop_psums={r['loop_psums']}")
    return rows


if __name__ == "__main__":
    main()
