"""Kernels: the paged attention kernel's share of its HBM roofline over
the window, in percent.

Bytes: each token the window decoded at context c (the keys it attends
to, itself included) needs the K and V of c positions in every layer,
``n_layers * 2 * n_kv_heads * head_dim * 2`` bytes (bf16 pools) per key.
A request's first token comes from its prefill, not the decode, and is
not counted. The kernel copies whole pages, so it moves at least these
bytes and the share cannot pass 100%. Time: the device seconds of the
kernel's calls (``paged_attention``) inside the window. ``None`` without
a trace, a window or the kernel.
"""
from bench.peaks import least_time_s

KERNEL = "paged_attention"
KV_BYTES = 2                            # bf16 pools


def read(ctx):
    tr, win = ctx["trace"], ctx["window"]
    if not tr or "out_lens" not in win.start:
        return None
    seconds = sum(k["seconds"] for k in tr["kernels"]
                  if k["kernel"] == KERNEL)
    if not seconds:
        return None
    cfg = ctx["system"].dims
    per_key = cfg["n_layers"] * 2 * cfg["n_kv_heads"] * cfg["head_dim"] \
        * KV_BYTES
    keys = 0
    for r, a, b in zip(win.extra["reqs"], win.start["out_lens"],
                       win.end["out_lens"]):
        p, lo = len(r.prompt), max(a, 1)
        if b > lo:                      # output k is decoded at p + k keys
            keys += (b - lo) * (2 * p + lo + b - 1) // 2
    if not keys:
        return None
    t = least_time_s(ctx["peaks"], bytes_moved=keys * per_key)
    return 100.0 * t / seconds
