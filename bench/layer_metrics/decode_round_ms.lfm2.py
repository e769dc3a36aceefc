"""Model step (Engine.decode): decode seconds per round, timed by the
engine up to ``block_until_ready``."""


def read(ctx):
    win = ctx["window"]
    if "decode_steps" not in win.start:
        return None
    rounds = win.delta("decode_steps")
    return win.delta("decode_time_s") * 1e3 / rounds if rounds else None
