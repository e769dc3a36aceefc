"""Device: share of the traced window in which no operation ran on the
chip (1 - busy / window), in percent."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
