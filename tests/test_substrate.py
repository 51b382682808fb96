"""Data pipeline, checkpointing, fault-tolerant runner, elastic policy,

sharding rules — the production substrate.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import abstract_mesh, make_mesh
from repro.checkpoint import checkpoint as ckpt
from repro.data import SyntheticLM, Prefetcher
from repro.runtime import Runner, RunnerConfig, StragglerMonitor, plan
from repro.sharding import partition


# --------------------------------------------------------------------------
# data
# --------------------------------------------------------------------------
def test_data_deterministic():
    p = SyntheticLM(vocab_size=100, seq_len=16, global_batch=4)
    b1 = p.batch(7)
    b2 = p.batch(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])


def test_data_elastic_resharding_exact():
    """The same global stream regardless of host count — the elastic
    restart guarantee."""
    kw = dict(vocab_size=1000, seq_len=8, global_batch=8, seed=3)
    full = SyntheticLM(num_hosts=1, host_id=0, **kw).global_batch_at(5)
    two = SyntheticLM(num_hosts=2, host_id=0, **kw)
    four = SyntheticLM(num_hosts=4, host_id=0, **kw)
    g2 = two.global_batch_at(5)
    g4 = four.global_batch_at(5)
    np.testing.assert_array_equal(full["tokens"], g2["tokens"])
    np.testing.assert_array_equal(g2["tokens"], g4["tokens"])


def test_data_hosts_disjoint():
    kw = dict(vocab_size=1000, seq_len=8, global_batch=8, num_hosts=4, seed=1)
    rows = [SyntheticLM(host_id=h, **kw).batch(0)["tokens"] for h in range(4)]
    flat = np.concatenate([r.reshape(-1, 8) for r in rows])
    assert len(np.unique(flat, axis=0)) == 8   # no duplicated samples


def test_prefetcher():
    p = SyntheticLM(vocab_size=50, seq_len=4, global_batch=2)
    pf = Prefetcher(p, start_step=0)
    s0, b0 = pf.next()
    s1, b1 = pf.next()
    pf.close()
    assert (s0, s1) == (0, 1)
    np.testing.assert_array_equal(b0["tokens"], p.batch(0)["tokens"])


# --------------------------------------------------------------------------
# checkpoint
# --------------------------------------------------------------------------
def _tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path)
    tree = _tree()
    ckpt.save(d, 3, tree, extra={"step": 3})
    restored, manifest = ckpt.restore(d, jax.tree.map(np.zeros_like, tree))
    np.testing.assert_array_equal(restored["a"], tree["a"])
    np.testing.assert_array_equal(restored["b"]["c"], tree["b"]["c"])
    assert manifest["extra"]["step"] == 3
    assert ckpt.latest_step(d) == 3


def test_checkpoint_corruption_detected(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree())
    # flip bytes in the shard
    shard = os.path.join(d, "step_00000001", "shard_00000.npz")
    data = np.load(shard)
    arrays = {k: data[k].copy() for k in data.files}
    arrays["leaf_0"][0, 0] += 999
    np.savez(shard, **arrays)
    with pytest.raises(IOError):
        ckpt.restore(d, _tree())


def test_checkpoint_structure_mismatch(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree())
    with pytest.raises(ValueError):
        ckpt.restore(d, {"x": np.zeros(3)})


def test_checkpoint_async_and_cleanup(tmp_path):
    d = str(tmp_path)
    cp = ckpt.AsyncCheckpointer(d)
    for s in (1, 2, 3, 4, 5):
        cp.save_async(s, _tree(), extra={"step": s})
    cp.wait()
    ckpt.cleanup(d, keep=2)
    steps = sorted(x for x in os.listdir(d) if x.startswith("step_"))
    assert steps == ["step_00000004", "step_00000005"]
    assert ckpt.latest_step(d) == 5


# --------------------------------------------------------------------------
# fault-tolerant runner
# --------------------------------------------------------------------------
def test_runner_recovers_from_injected_failure(tmp_path):
    """Fail at step 7, restore at 5, finish at 12 with correct state."""
    calls = {"failures_left": 1}

    def build_step(mesh):
        def step(state, batch):
            if batch["step"] == 7 and calls["failures_left"] > 0:
                calls["failures_left"] -= 1
                raise RuntimeError("injected device loss")
            return {"x": state["x"] + 1.0}, {"loss": float(state["x"])}
        return step

    runner = Runner(
        config=RunnerConfig(checkpoint_dir=str(tmp_path),
                            checkpoint_every=5, max_failures=2),
        make_mesh=lambda f: f"mesh_after_{f}_failures",
        build_step=build_step,
        init_state=lambda mesh: {"x": jnp.zeros(())},
        batch_for=lambda step, mesh: {"step": step},
    )
    state, step = runner.run(12)
    assert step == 12
    assert runner.failures == 1
    # x counts executed steps; after restore-at-5 it re-runs 5..11
    assert float(state["x"]) == 12.0


def test_straggler_monitor():
    mon = StragglerMonitor(window=20, zscore=3.0, min_samples=5)
    flagged = [mon.record(i, 0.1 + 0.001 * (i % 3)) for i in range(20)]
    assert not any(flagged)
    assert mon.record(20, 1.5) is True
    assert mon.flagged[0][0] == 20


def test_straggler_monitor_keeps_only_its_window():
    mon = StragglerMonitor(window=8, zscore=3.0, min_samples=5)
    for i in range(1000):
        mon.record(i, 0.1 + 0.001 * (i % 3))
    assert len(mon.times) == 8
    # A slow step is still judged against the last ``window`` steps.
    assert mon.record(1000, 1.5) is True
    assert [f[0] for f in mon.flagged] == [1000]


def test_elastic_plan():
    p = plan(512, model_parallel=16, global_batch=256, want_pods=2)
    assert p.mesh_shape == (2, 16, 16)
    assert p.grad_accum == 1
    # lose a host: 496 devices don't divide -> shrink data axis
    p2 = plan(480, model_parallel=16, global_batch=256, want_pods=2)
    assert p2.mesh_shape[2] == 16
    total = p2.mesh_shape[0] * p2.mesh_shape[1] * p2.mesh_shape[2]
    assert total == 480
    assert p2.global_batch * p2.grad_accum >= 240
    with pytest.raises(ValueError):
        plan(100, model_parallel=16, global_batch=64)


# --------------------------------------------------------------------------
# sharding rules
# --------------------------------------------------------------------------
def test_partition_rules():
    mesh = make_mesh((1, 1), ("data", "model"))
    P = jax.sharding.PartitionSpec
    abstract = {
        "embed": jax.ShapeDtypeStruct((1024, 512), jnp.float32),
        "layers": {"attn": {"wq": jax.ShapeDtypeStruct((8, 512, 512),
                                                       jnp.float32)}},
        "norm": jax.ShapeDtypeStruct((512,), jnp.float32),
    }
    sh = partition.param_shardings(mesh, abstract)
    assert sh["embed"].spec == P("model", ("data",))
    assert sh["layers"]["attn"]["wq"].spec == P(None, ("data",), "model")
    assert sh["norm"].spec == P()


def test_moe_expert_sharding_adaptive():
    """EP when E divides the model axis; TP-within-expert otherwise."""
    P = jax.sharding.PartitionSpec
    mesh16 = abstract_mesh((1, 16), ("data", "model"))
    # 128 experts / 16-way: EP on the expert dim
    spec = partition._resolve(mesh16, partition.PARAM_RULES,
                              "layers/moe/w_gate", (24, 128, 512, 1024))
    assert spec == P(None, "model", ("data",), None)
    # 8 experts / 16-way: fall back to TP on the hidden dim (SSPerf h1 iter1)
    spec = partition._resolve(mesh16, partition.PARAM_RULES,
                              "layers/moe/w_gate", (56, 8, 512, 1024))
    assert spec == P(None, None, ("data",), "model")
    spec = partition._resolve(mesh16, partition.PARAM_RULES,
                              "layers/moe/w_down", (56, 8, 1024, 512))
    assert spec == P(None, None, "model", ("data",))


def test_kv_cache_sharding_adaptive():
    """heads over model when divisible; else slots (flash-decoding)."""
    P = jax.sharding.PartitionSpec
    mesh16 = abstract_mesh((1, 16), ("data", "model"))
    spec = partition._resolve(mesh16, partition.CACHE_RULES, "cache/k",
                              (40, 128, 16, 32768, 128), batch_axes="data")
    assert spec == P(None, "data", "model", None, None)
    # 4 kv heads don't divide 16 -> shard the 32768 slots (SSPerf h2 iter1)
    spec = partition._resolve(mesh16, partition.CACHE_RULES, "cache/k",
                              (22, 128, 4, 32768, 64), batch_axes="data")
    assert spec == P(None, "data", None, "model", None)


def test_partition_divisibility_guard():
    mesh = make_mesh((1, 1), ("data", "model"))
    # 12 heads * 64 = 768 divides 1; but a dim of 7 can't shard on 16...
    # simulate with a 16-way mesh via spec resolution only
    spec = partition._resolve(mesh, partition.PARAM_RULES, "attn/wq",
                              (7, 7))
    assert spec == jax.sharding.PartitionSpec(None, None) or \
        spec == jax.sharding.PartitionSpec(("data",), "model")


def test_batch_axes_for():
    mesh = abstract_mesh((2, 2, 1), ("pod", "data", "model"))
    assert partition.batch_axes_for(mesh, 8) == ("pod", "data")
    assert partition.batch_axes_for(mesh, 2) == ("data",)
    assert partition.batch_axes_for(mesh, 1) is None


def test_roofline_collective_parser():
    from repro.roofline import parse_collectives
    hlo = """
      %ag = bf16[128,4096]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}
      %ar = f32[1024]{0} all-reduce(%y), replica_groups=[4,16]<=[64]
      %cp = f32[256]{0} collective-permute(%z)
      %add = f32[2]{0} add(%a, %b)
    """
    ops = parse_collectives(hlo)
    kinds = {o.kind for o in ops}
    assert kinds == {"all-gather", "all-reduce", "collective-permute"}
    ag = next(o for o in ops if o.kind == "all-gather")
    assert ag.result_bytes == 128 * 4096 * 2
    assert ag.group_size == 4
    ar = next(o for o in ops if o.kind == "all-reduce")
    assert ar.group_size == 16


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the device kind JAX reports; an unknown kind is an
    error, never v5e's numbers."""
    from repro.roofline import chip_peaks
    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("TPU v4")
