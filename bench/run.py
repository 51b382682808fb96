#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the TPU this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the check compared, beside
its limit.  The same checks are the last lines of standard error.

Without a TPU, with fewer chips than the cell asks for, with a device kind
that ``bench/peaks.json`` does not list, or without the program under
``src/``, it exits non-zero and prints no result.  JAX's persistent
compilation cache is placed by the program's ``compile_cache.configure``:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` in the
checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

# Tile choices stay in memory: a tile recorded by another process must not
# change what this one compiles.
os.environ.setdefault("REPRO_TUNE_CACHE", "off")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_KERNELS"):
        print("bench: REPRO_KERNELS is set; the benchmark runs the kernels' "
              "own dispatch", file=sys.stderr)
        return 2
    try:
        from repro.runtime import compile_cache
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    import jax

    from bench import harness, roofline

    cache_dir = compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = harness.CompileCounter()
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_process=T_PROCESS, counter=counter)
    except (harness.NoChip, roofline.UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    dev = result["device"]
    print(f"bench: {args.workload} seed {args.seed} on {dev['platform']} "
          f"{dev['kind']} x{dev['count']}; compile cache {cache_dir}; "
          f"compiles in the window: {result.get('window_compiles')}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
