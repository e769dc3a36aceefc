"""Kernels: the expert layer's kernel (``moe_experts``) at its HBM
roofline over the window, in percent.

Bytes: ``bench/expert_bytes.py`` from the window's Δ``serve.moe_experts_hit``
(experts given at least one token, summed over every expert-layer call:
decode rounds and the admissions' prefills) and Δ``serve.moe_assignments``.
Time: the device seconds of the kernel's calls inside the window. ``None``
without a trace, the counters (a program that has none) or the kernel.
"""
from bench.expert_bytes import expert_bytes
from bench.peaks import least_time_s

KERNEL = "moe_experts"


def read(ctx):
    tr, win = ctx["trace"], ctx["window"]
    if not tr or "moe_experts_hit" not in win.start:
        return None
    seconds = sum(k["seconds"] for k in tr["kernels"]
                  if k["kernel"] == KERNEL)
    hit = win.delta("moe_experts_hit")
    if not seconds or not hit:
        return None
    conf = ctx["conf"]
    moved = expert_bytes(hit, win.delta("moe_assignments"),
                         conf["hidden_size"], conf["moe_intermediate_size"])
    return 100.0 * least_time_s(ctx["peaks"], bytes_moved=moved) / seconds
