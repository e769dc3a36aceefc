"""95th percentile, over every request due in the window, of first token
minus due time; a request that failed or never finished is a miss (+inf)."""
import math

from bench.stats import percentile


def read(ctx):
    win, system = ctx["window"], ctx["system"]
    if not win.extra.get("open_loop"):
        return None
    due = dict(zip((r.uid for r in win.extra["reqs"]), win.extra["due_abs"]))
    vals = [(r.first_token_t - due[r.uid]) * 1e3 if r.status == "ok"
            else math.inf for r in system.window_requests(win)]
    return percentile(vals, 95)
