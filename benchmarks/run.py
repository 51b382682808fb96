"""Benchmark driver — one section per paper table/figure + framework extras.

    PYTHONPATH=src python -m benchmarks.run            # quick
    PYTHONPATH=src python -m benchmarks.run --full     # paper's full N sweep

Sections:
  [1] gmres_strategies   — paper Table 1 / Figure 5 analogue
  [2] kernel_bench       — Pallas kernel layer (wall CPU + TPU structural)
  [3] distributed_gmres  — sharded-solver scaling + collective schedule
  [4] roofline_table     — SSRoofline terms for every dry-run cell
"""
from __future__ import annotations

import sys


def main() -> None:
    full = "--full" in sys.argv
    from repro.runtime import compile_cache
    compile_cache.configure()
    from benchmarks import (distributed_gmres, gmres_strategies,
                            kernel_bench, roofline_table)

    print("# [1] GMRES offload strategies (paper Table 1 analogue)")
    gmres_strategies.main(full=full)
    print()
    print("# [2] kernel layer")
    kernel_bench.main()
    print()
    print("# [3] distributed GMRES (8-way row-sharded, fake devices)")
    distributed_gmres.main()
    print()
    print("# [4] roofline terms from the multi-pod dry-run")
    roofline_table.main()


if __name__ == "__main__":
    main()
