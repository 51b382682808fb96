import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.
# This flag lives ONLY here — smoke tests and benches see the real 1 device.

_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds abstract params / optimizer state / batch / cache
     (ShapeDtypeStruct only — nothing is allocated),
  3. jits the train/prefill/serve step with explicit NamedShardings,
  4. ``.lower().compile()`` — a sharding mismatch, an unsupported
     collective, or an at-compile OOM is a FAILURE of the framework,
  5. records memory_analysis / cost_analysis / the collective schedule and
     the three roofline terms as a JSON line.

Usage:
    python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
    python -m repro.launch.dryrun --all --out results/dryrun.jsonl
    python -m repro.launch.dryrun --arch mixtral-8x22b --shape decode_32k \
        --multi-pod
"""
__doc__ = _DOC

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax


from repro import configs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (abstract_state, make_optimizer,
                                make_prefill_step, make_serve_step,
                                make_train_step, state_shardings)
from repro.models import (SHAPES, active_param_count, build, cache_specs,
                          input_specs, shape_applicable)
from repro.roofline import analyze, chip_peaks, model_flops_for

# The chip the production meshes model (their devices here are CPU stand-ins).
TARGET_KIND = "TPU v5 lite"


def _depth_variant(cfg, k: int):
    """Same arch with a k-unit-deep scan (unit = one scan iteration)."""
    import dataclasses as dc
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return dc.replace(cfg, num_layers=k)
    if fam == "moe":
        return dc.replace(cfg, num_layers=k * cfg.moe_every)
    if fam == "encdec":
        return dc.replace(cfg, num_layers=k, encoder_layers=k)
    if fam == "hybrid":
        return dc.replace(cfg, num_layers=k * cfg.attn_every)
    if fam == "ssm":
        return dc.replace(cfg, num_layers=k * cfg.slstm_every)
    raise ValueError(fam)


def _depth_units(cfg) -> float:
    fam = cfg.family
    if fam in ("dense", "vlm", "encdec"):
        return float(cfg.num_layers)
    if fam == "moe":
        return cfg.num_layers / cfg.moe_every
    if fam == "hybrid":
        return cfg.num_layers / cfg.attn_every
    if fam == "ssm":
        return cfg.num_layers / cfg.slstm_every
    raise ValueError(fam)


def _lower_one(cfg, shape, mesh):
    """Lower + compile a single program for (cfg, shape) on mesh."""
    if shape.kind == "train":
        fn, _, _ = make_train_step(cfg, mesh, shape)
        opt = make_optimizer(cfg)
        return fn.lower(abstract_state(cfg, opt), input_specs(cfg, shape))
    if shape.kind == "prefill":
        fn, _, _ = make_prefill_step(cfg, mesh, shape)
        ab_params = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
        return fn.lower(ab_params, input_specs(cfg, shape))
    fn, _, _ = make_serve_step(cfg, mesh, shape)
    ab_params = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    ab_cache = cache_specs(cfg, shape)
    ab_tok = jax.ShapeDtypeStruct((shape.global_batch,), "int32")
    ab_pos = jax.ShapeDtypeStruct((), "int32")
    return fn.lower(ab_params, ab_cache, ab_tok, ab_pos)


def _stats_of(compiled):
    from repro.roofline import parse_collectives
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(sum(c.result_bytes * c.count for c in colls)),
        "coll_s": float(sum(c.ring_seconds(chip_peaks(TARGET_KIND).link_bw)
                            * c.count for c in colls)),
    }


def depth_corrected_stats(cfg, shape, mesh, full_stats):
    """XLA's cost analysis attributes ~ZERO cost to while/scan BODIES
    (verified: granite-3-8b train FLOPs are depth-invariant at 1/2/4
    layers — EXPERIMENTS.md SSPerf iteration 0).  So the full program's
    numbers cover only the non-scanned base (embeddings, lm head, loss,
    optimizer), and each scan unit is compiled STANDALONE with identical
    shardings and added in: total = base + sum units x unit (unitcost.py).
    """
    from repro.launch.unitcost import composed_stats
    total, detail = composed_stats(cfg, shape, mesh, full_stats)
    return total, {"base": full_stats, "units": detail}


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               cfg_override=None, correct_depth: bool = True):
    """Lower + compile one cell; returns (record dict, compiled)."""
    cfg = cfg_override or configs.get(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}, None

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()

    with mesh:
        lowered = _lower_one(cfg, shape, mesh)
        compiled = lowered.compile()
        compile_s = time.time() - t0

        raw = _stats_of(compiled)
        if correct_depth:
            corrected, depth_info = depth_corrected_stats(cfg, shape, mesh,
                                                          raw)
        else:
            corrected, depth_info = raw, {}

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        bytes_per_device = getattr(mem, "output_size_in_bytes", None)
        mem_record = {
            k: getattr(mem, k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        }
    except Exception:
        bytes_per_device = None
        mem_record = {}
    hlo = compiled.as_text()

    roof = analyze(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
        cost={"flops": corrected["flops"],
              "bytes accessed": corrected["bytes"]},
        hlo_text=hlo,
        model_flops=model_flops_for(cfg, shape, active_param_count(cfg)),
        device_kind=TARGET_KIND, bytes_per_device=bytes_per_device)
    # override HLO-text collective stats with the depth-corrected ones
    roof = dataclasses.replace(roof,
                               collective_bytes=corrected["coll_bytes"],
                               collective_s=corrected["coll_s"])
    terms = {"compute": roof.compute_s, "memory": roof.memory_s,
             "collective": roof.collective_s}
    roof = dataclasses.replace(roof, bottleneck=max(terms, key=terms.get))

    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "chips": chips, "compile_s": round(compile_s, 1),
        "memory_analysis": mem_record,
        "cost_flops_raw": cost.get("flops"),
        "cost_bytes_raw": cost.get("bytes accessed"),
        "depth_correction": depth_info,
        "roofline": dataclasses.asdict(roof),
        "hlo_bytes": len(hlo),
    }
    return record, compiled


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all (arch x shape) cells on the chosen mesh")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for arch in configs.ARCH_IDS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    failures = 0
    for arch, shape in cells:
        try:
            record, _ = lower_cell(arch, shape, multi_pod=args.multi_pod)
        except Exception as e:
            record = {"arch": arch, "shape": shape,
                      "mesh": "2x16x16" if args.multi_pod else "16x16",
                      "status": "error", "error": repr(e),
                      "trace": traceback.format_exc()[-2000:]}
            failures += 1
        line = json.dumps(record)
        print(line if record["status"] != "ok" else
              f"OK {arch} {shape} {record['mesh']} "
              f"compile={record['compile_s']}s "
              f"bottleneck={record['roofline']['bottleneck']}")
        sys.stdout.flush()
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
