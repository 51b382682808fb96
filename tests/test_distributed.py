"""Distributed GMRES + sharded step lowering on fake devices.

The 8-device cases run in a subprocess because the XLA host-device-count
flag must be set before jax initializes (the main pytest process keeps the
real 1-device view, as required).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_gmres_matches_dense_8dev():
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.compat import make_mesh
        from repro.core import gmres, gmres_sharded, operators
        mesh = make_mesh((8,), ('model',))
        a = operators.random_diagdom(jax.random.PRNGKey(0), 256)
        b = jax.random.normal(jax.random.PRNGKey(1), (256,))
        res_d = gmres_sharded(mesh, 'model', a, b, m=20, tol=1e-5)
        res_s = gmres(a, b, m=20, tol=1e-5)
        err = float(jnp.linalg.norm(res_d.x - res_s.x)
                    / jnp.linalg.norm(res_s.x))
        rel = float(jnp.linalg.norm(a @ res_d.x - b) / jnp.linalg.norm(b))
        print(json.dumps({"err": err, "rel": rel,
                          "conv": bool(res_d.converged),
                          "restarts": int(res_d.restarts)}))
    """)
    r = _run_subprocess(code)
    assert r["conv"]
    assert r["rel"] < 5e-5
    assert r["err"] < 1e-3


def test_train_step_runs_on_2x4_mesh():
    """REAL sharded train step executes (not just lowers) on 8 fake devices."""
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh
        from repro import configs
        from repro.launch.steps import make_train_step, TrainState, \\
            make_optimizer
        from repro.models import build
        from repro.models.config import ShapeConfig
        mesh = make_mesh((2, 4), ('data', 'model'))
        cfg = configs.get('tinyllama-1.1b').reduced()
        shape = ShapeConfig('t', 32, 4, 'train')
        opt = make_optimizer(cfg)
        step_fn, st_sh, b_sh = make_train_step(cfg, mesh, shape, opt=opt)
        model = build(cfg)
        with mesh:
            params = jax.jit(model.init, out_shardings=st_sh.params)(
                jax.random.PRNGKey(0))
            opt_state = jax.jit(opt.init, out_shardings=st_sh.opt)(params)
            batch = {
              'tokens': jnp.ones((4, 32), jnp.int32),
              'labels': jnp.ones((4, 32), jnp.int32),
              'mask': jnp.ones((4, 32), jnp.float32),
            }
            batch = jax.device_put(batch, b_sh)
            state = TrainState(params=params, opt=opt_state)
            losses = []
            for _ in range(3):
                state, m = step_fn(state, batch)
                losses.append(float(m['loss']))
        print(json.dumps({"losses": losses}))
    """)
    r = _run_subprocess(code)
    assert all(np.isfinite(r["losses"]))
    assert r["losses"][-1] < r["losses"][0]    # optimizes a repeated batch


def test_serve_step_runs_on_2x4_mesh():
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.compat import make_mesh
        from repro import configs
        from repro.launch.steps import make_serve_step
        from repro.models import build
        from repro.models.config import ShapeConfig
        mesh = make_mesh((2, 4), ('data', 'model'))
        cfg = configs.get('mixtral-8x22b').reduced()
        shape = ShapeConfig('d', 64, 4, 'decode')
        model = build(cfg)
        serve, p_sh, _ = make_serve_step(cfg, mesh, shape)
        with mesh:
            params = jax.jit(model.init, out_shardings=p_sh)(
                jax.random.PRNGKey(0))
            cache = model.init_cache(4, 64)
            tok = jnp.array([2, 3, 4, 5], jnp.int32)
            outs = []
            for i in range(4):
                tok, cache = serve(params, cache, tok, jnp.int32(i))
                outs.append(int(tok[0]))
        print(json.dumps({"tokens": outs}))
    """)
    r = _run_subprocess(code)
    assert len(r["tokens"]) == 4


def test_sharded_block_jacobi_cuts_steps_8dev():
    """Shard-local block-Jacobi: large step (= collective-round) reduction
    with zero preconditioner communication (SSPerf hillclimb 3)."""
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.compat import make_mesh
        from repro.core import gmres_sharded, operators
        mesh = make_mesh((8,), ('model',))
        n = 1024
        a = operators.convection_diffusion(n, beta=0.7)
        b = jnp.sin(jnp.arange(n) * 0.1)
        base = gmres_sharded(mesh, 'model', a, b, m=20, tol=1e-4,
                             max_restarts=300)
        pc = gmres_sharded(mesh, 'model', a, b, m=20, tol=1e-4,
                           max_restarts=300, precond='block_jacobi')
        bn = float(jnp.linalg.norm(b))
        print(json.dumps({
            "base_steps": int(base.inner_steps),
            "pc_steps": int(pc.inner_steps),
            "pc_rel": float(pc.residual) / bn,
            "pc_conv": bool(pc.converged)}))
    """)
    r = _run_subprocess(code)
    assert r["pc_conv"]
    assert r["pc_rel"] < 5e-4
    assert r["pc_steps"] * 20 < r["base_steps"]   # >=20x fewer rounds


def test_compressed_psum_8dev():
    """int8 compressed all-reduce ~= f32 psum within quantization error."""
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh
        from repro.optim.compression import compressed_psum
        mesh = make_mesh((8,), ('d',))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 1024))

        def f(xs):
            exact = jax.lax.psum(xs, 'd')
            approx = compressed_psum(xs, 'd')
            err = jnp.linalg.norm(exact - approx) / jnp.linalg.norm(exact)
            return err[None]
        err = jax.shard_map(f, mesh=mesh,
                            in_specs=jax.sharding.PartitionSpec('d'),
                            out_specs=jax.sharding.PartitionSpec('d'),
                            check_vma=False)(x)
        print(json.dumps({"err": float(jnp.max(err))}))
    """)
    r = _run_subprocess(code)
    assert r["err"] < 2e-2


def test_singleton_mesh_inprocess():
    """shard_map solver on the real (1-device) mesh — no subprocess."""
    from repro.core import gmres_sharded, operators
    mesh = make_mesh((1,), ("model",))
    a = operators.random_diagdom(jax.random.PRNGKey(0), 64)
    b = jax.random.normal(jax.random.PRNGKey(1), (64,))
    res = gmres_sharded(mesh, "model", a, b, m=16, tol=1e-5)
    assert bool(res.converged)
    err = float(jnp.linalg.norm(a @ res.x - b) / jnp.linalg.norm(b))
    assert err < 5e-5
