"""Paper Fig 3: ratio of memory accesses (scalar path / im2col path),
normalized per MAC — the data-reuse quantity that explains the varying SIMD
speedup. Analytic counters from core/energy, swept over the same Table-2
experiment plan."""
from __future__ import annotations

from repro.core import ConvSpec, accesses_direct, accesses_im2col, reuse_ratio

from .common import emit
from .sweeps import EXPERIMENTS, PRIMS, spec_for


def main():
    for exp_name, (pname, values, fixed) in EXPERIMENTS.items():
        for prim in PRIMS:
            for v in values:
                cfg = dict(fixed)
                cfg[pname] = v
                spec = spec_for(prim, cfg["kernel_size"], cfg["cin"],
                                cfg["cout"], cfg.get("groups", 1))
                w = cfg["width"]
                macs = spec.mac_count(w)
                a_d = accesses_direct(spec, w)
                a_i = accesses_im2col(spec, w)
                emit(f"fig3/{exp_name}/{prim}/{pname}={v}", 0.0,
                     f"acc_per_mac_scalar={a_d/macs:.3f} "
                     f"acc_per_mac_im2col={a_i/macs:.3f} "
                     f"reuse_ratio={reuse_ratio(spec, w):.3f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
