"""serve_latency_ms_p50: the median (nearest rank) over every request due
in the window, each timed from its due time to the moment the benchmark
sees its outcome.  A request that failed or never came back counts at the
time it had waited when the run ended."""

import math


def read(run):
    if not run.requests:
        return None
    waits = sorted(r["wait"] if r["status"] == "done" else r["late"]
                   for r in run.requests)
    return waits[math.ceil(0.50 * len(waits)) - 1] * 1e3
