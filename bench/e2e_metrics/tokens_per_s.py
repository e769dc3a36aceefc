"""Output tokens delivered in the window (``serve.tokens_out`` read at its
edges) over the window's seconds."""


def read(ctx):
    win = ctx["window"]
    return win.delta("tokens_out") / win.seconds
