"""Model step: the least compute time of the model operations the window's
tokens needed (prompts prefilled, tokens decoded, each at its context
length; an int8 FFN at the int8 peak, everything else at the bf16 peak)
over the window, in percent."""
from bench import counts
from bench.peaks import least_time_s


def read(ctx):
    win, system = ctx["window"], ctx["system"]
    if "out_lens" not in win.start:
        return None
    cfg = system.dims
    ffn = "bf16" if system.scfg_kw["precision"] == "float" else "int8"
    head = 2.0 * cfg["d_model"] * cfg["vocab"]
    bf16 = int8 = 0.0
    for r, a, b in zip(win.extra["reqs"], win.start["out_lens"],
                       win.end["out_lens"]):
        if b <= a:
            continue
        p = len(r.prompt)
        if a == 0:                      # prefilled inside the window
            c = counts.lm_sequence(cfg, 0, p, ffn)
            bf16 += c["bf16_flops"] - (p - 1) * head
            int8 += c["int8_ops"]
        c = counts.lm_sequence(cfg, p + max(a, 1) - 1, p + b - 1, ffn)
        bf16 += c["bf16_flops"]
        int8 += c["int8_ops"]
    if not bf16:
        return None
    t = least_time_s(ctx["peaks"], bf16_flops=bf16, int8_ops=int8)
    return 100.0 * t / win.seconds
