"""95th percentile, over every request due in the window, of (finish -
first token) / (tokens - 1); a request that failed or never finished is a
miss (+inf)."""
import math

from bench.stats import percentile


def read(ctx):
    win, system = ctx["window"], ctx["system"]
    if not win.extra.get("open_loop"):
        return None
    vals = [(r.finish_t - r.first_token_t) * 1e3 / (len(r.out_tokens) - 1)
            if r.status == "ok" and len(r.out_tokens) > 1 else math.inf
            for r in system.window_requests(win)]
    return percentile(vals, 95)
