"""§Roofline: per (arch x shape x mesh) table from the dry-run artifacts.

compute_s    = HLO dot FLOPs (trip-corrected) / 197 TF/s
memory_s     = min(analytic traffic, HLO out-bytes proxy) / 819 GB/s
               [out-bytes counts every op output = unfused upper bound;
                analytic = params + activation checkpoints + KV, the fused
                lower bound — both are reported]
collective_s = ICI bytes / (4 links x 50 GB/s) + DCN bytes / 25 GB/s
"""
from __future__ import annotations

import glob
import json
import os

from .common import emit

PEAK = 197e12
HBM = 819e9
ICI = 4 * 50e9
DCN = 25e9

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def load(tag="baseline"):
    rows = []
    for p in sorted(glob.glob(os.path.join(ART, f"*__{tag}.json"))):
        with open(p) as f:
            rows.append(json.load(f))
    return rows


def terms(rec):
    h = rec["hlo"]
    comp = h["dot_flops"] / PEAK
    mem_hi = h["out_bytes"] / HBM
    # analytic floor: every argument byte touched once + outputs
    mem_lo = (rec["memory"]["argument_bytes"] + rec["memory"]["output_bytes"]) / HBM
    coll = h["coll_bytes_ici"] / ICI + h["coll_bytes_dcn"] / DCN
    # fused memory estimate classifies the bottleneck (see scripts/report.py)
    dom = max((comp, "compute"), (mem_lo, "memory"), (coll, "collective"))
    useful = rec["model_flops"] / max(rec["n_chips"] * h["dot_flops"], 1.0)
    return dict(compute_s=comp, memory_s_upper=mem_hi, memory_s_lower=mem_lo,
                collective_s=coll, bottleneck=dom[1],
                flops_ratio=min(useful, 9.99),
                roofline_frac=min(rec["model_flops"] / rec["n_chips"] / PEAK
                                  / max(dom[0], 1e-12), 9.99))


def main(tag="baseline"):
    rows = load(tag)
    ok = [r for r in rows if r.get("status") == "ok"]
    skipped = [r for r in rows if r.get("status") == "skipped"]
    errors = [r for r in rows if r.get("status") == "error"]
    for r in ok:
        t = terms(r)
        peak_tpu = r["memory"].get("peak_bytes_tpu", r["memory"]["peak_bytes"])
        emit(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}", 0.0,
             f"compute_s={t['compute_s']:.4f} "
             f"memory_s={t['memory_s_lower']:.4f}..{t['memory_s_upper']:.4f} "
             f"collective_s={t['collective_s']:.4f} "
             f"bottleneck={t['bottleneck']} "
             f"model/hlo_flops={t['flops_ratio']:.3f} "
             f"roofline_frac={t['roofline_frac']:.3f} "
             f"peak_GiB={peak_tpu/2**30:.2f}")
    for r in skipped:
        emit(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}", 0.0, "SKIPPED")
    for r in errors:
        emit(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}", 0.0,
             f"ERROR {r.get('error','')[:90]}")
    emit("roofline/summary", 0.0,
         f"ok={len(ok)} skipped={len(skipped)} errors={len(errors)}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
