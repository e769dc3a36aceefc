"""Serving layer (BlockPool): prompt pages found in the prefix cache over
pages looked up, summed over every drain's pool inside the window, in
percent."""


def read(ctx):
    win = ctx["window"]
    if "pool_lookups" not in win.start:
        return None
    looked = win.delta("pool_lookups")
    return 100.0 * win.delta("pool_hits") / looked if looked else None
