"""Serving layer: 95th percentile over the requests due in the window of
admission minus due time (never admitted: +inf)."""
import math

from bench.stats import percentile


def read(ctx):
    win, system = ctx["window"], ctx["system"]
    if not win.extra.get("open_loop"):
        return None
    due = dict(zip((r.uid for r in win.extra["reqs"]), win.extra["due_abs"]))
    vals = [(r.admit_t - due[r.uid]) * 1e3 if r.admit_t > 0 else math.inf
            for r in system.window_requests(win)]
    return percentile(vals, 95)
