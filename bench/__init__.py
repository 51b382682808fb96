"""Chip benchmark of the GMRES library and its solver server.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU it is
started on and prints one JSON result line.  Configurations, traffic mixes
and metrics are files under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, found by the names in ``BENCHMARK.json``.
"""
