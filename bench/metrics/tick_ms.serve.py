"""tick_ms.serve: mean wall time of the benchmark's span around each
``SolverServer.step`` call inside the window that ran a cycle."""


def read(run):
    ticks = [t for t in run.ticks if t["cycle"] and t["t0"] < run.window_s]
    if not ticks:
        return None
    return sum(t["t1"] - t["t0"] for t in ticks) / len(ticks) * 1e3
