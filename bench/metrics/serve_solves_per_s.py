"""serve_solves_per_s: requests completed DONE inside the window, over the
window's seconds."""


def read(run):
    if not run.requests:
        return None
    done = sum(r["status"] == "done" and r["done"] <= run.window_s
               for r in run.requests)
    return done / run.window_s
