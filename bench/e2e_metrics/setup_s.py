"""Set-up time: process start to the window's start (loading, weights,
compilation or cache loads, warm-up), on the host clock."""


def read(ctx):
    return ctx["setup_s"]
