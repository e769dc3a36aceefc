"""Model step (Engine.prefill / prefill_suffix): mean of first token minus
admission over the requests due in the window that were served."""


def read(ctx):
    win, system = ctx["window"], ctx["system"]
    if not win.extra.get("open_loop"):
        return None
    vals = [(r.first_token_t - r.admit_t) * 1e3
            for r in system.window_requests(win) if r.status == "ok"]
    return sum(vals) / len(vals) if vals else None
