"""Paper Fig 2: per-primitive sweeps over groups / kernel / width / channels
/ filters (Table 2 plan), measuring

  * theoretical MACs (Table 1 formulas),
  * measured CPU latency of the DIRECT path (scalar analogue: explicit
    shifted-multiply accumulation, no matrix engine) vs the IM2COL/engine
    path (lax.conv -> Eigen im2col+GEMM; the TPU analogue is the MXU
    Pallas kernel, benchmarked in optlevel.py),
  * modeled MCU latency & energy with/without SIMD (core/energy, constants
    calibrated to the paper's Table 3),

and reproducing the paper's regression claims:
  (a) no-SIMD: MACs <-> energy is linear (r~0.999),
  (b) SIMD: latency predicts energy better than MACs do (Fig 2 d/e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ConvSpec, MCUModel, init, apply
from repro.core.primitives import shift_channels, add_conv

from .common import FAST, emit, r_squared, time_fn

EXPERIMENTS = {
    # name: (sweep_param, values, fixed)
    "exp1_groups": ("groups", [1, 2, 4, 8] if FAST else [1, 2, 4, 8, 16, 32],
                    dict(kernel_size=3, width=10, cin=128, cout=64)),
    "exp2_kernel": ("kernel_size", [1, 3, 5] if FAST else [1, 3, 5, 7, 9, 11],
                    dict(groups=2, width=32, cin=16, cout=16)),
    "exp3_width": ("width", [8, 16] if FAST else [8, 16, 24, 32],
                   dict(groups=2, kernel_size=3, cin=16, cout=16)),
    "exp4_cin": ("cin", [4, 16] if FAST else [4, 8, 16, 32],
                 dict(groups=2, kernel_size=3, width=32, cout=16)),
    "exp5_cout": ("cout", [4, 16] if FAST else [4, 8, 16, 32],
                  dict(groups=2, kernel_size=3, width=32, cin=16)),
}

PRIMS = ("standard", "grouped", "dws", "shift", "add")


def direct_forward(params, x, spec: ConvSpec):
    """Scalar-path analogue: explicit shifted multiply-accumulate, no dot."""
    hk = spec.kernel_size
    ph, pw = hk // 2, (hk - 1) // 2

    def conv_direct(xx, w):
        cx, cy = w.shape[2], w.shape[3]
        xp = jnp.pad(xx, ((0, 0), (ph, pw), (ph, pw), (0, 0)))
        h = xx.shape[1]
        out = jnp.zeros(xx.shape[:3] + (cy,), xx.dtype)
        for i in range(hk):
            for j in range(hk):
                patch = xp[:, i:i + h, j:j + h, :]
                out = out + jnp.sum(patch[..., None] * w[i, j][None, None, None],
                                    axis=3)
        return out

    p = spec.primitive
    if p == "standard":
        return conv_direct(x, params["w"])
    if p == "grouped":
        cg = spec.in_channels // spec.groups
        outs = []
        per = spec.out_channels // spec.groups
        for g in range(spec.groups):
            outs.append(conv_direct(x[..., g * cg:(g + 1) * cg],
                                    params["w"][..., g * per:(g + 1) * per]))
        return jnp.concatenate(outs, axis=-1)
    if p == "dws":
        h = jnp.zeros_like(x)
        xp = jnp.pad(x, ((0, 0), (ph, pw), (ph, pw), (0, 0)))
        for i in range(hk):
            for j in range(hk):
                h = h + xp[:, i:i + x.shape[1], j:j + x.shape[2], :] \
                    * params["w_dw"][i, j, :, 0][None, None, None]
        return jnp.sum(h[..., None] * params["w_pw"][0, 0][None, None, None],
                       axis=3)
    if p == "shift":
        s = shift_channels(x, params["shifts"],
                           max_shift=spec.kernel_size // 2)
        return jnp.sum(s[..., None] * params["w_pw"][0, 0][None, None, None],
                       axis=3)
    if p == "add":
        return add_conv(x, params["w"])
    raise ValueError(p)


def spec_for(prim, kernel_size, cin, cout, groups):
    g = groups if prim == "grouped" else 1
    while cin % g or cout % g:
        g //= 2
    return ConvSpec(primitive=prim, in_channels=cin, out_channels=cout,
                    kernel_size=1 if prim in () else kernel_size,
                    groups=max(g, 1), use_bias=False)


def main():
    mcu = MCUModel()
    rows = []
    key = jax.random.PRNGKey(0)
    for exp_name, (pname, values, fixed) in EXPERIMENTS.items():
        for prim in PRIMS:
            for v in values:
                cfg = dict(fixed)
                cfg[pname] = v
                spec = spec_for(prim, cfg["kernel_size"], cfg["cin"],
                                cfg["cout"], cfg.get("groups", 1))
                width = cfg["width"]
                params = init(key, spec)
                x = jax.random.normal(key, (1, width, width, spec.in_channels))
                f_direct = jax.jit(functools.partial(direct_forward, spec=spec))
                f_engine = jax.jit(functools.partial(apply, spec=spec))
                us_d = time_fn(f_direct, params, x, reps=3, warmup=1)
                us_e = time_fn(f_engine, params, x, reps=3, warmup=1)
                macs = spec.mac_count(width)
                lat_s = mcu.latency_s(spec, width, simd=False)
                e_s = mcu.energy_mj(spec, width, simd=False)
                lat_v = mcu.latency_s(spec, width, simd=True)
                e_v = mcu.energy_mj(spec, width, simd=True)
                rows.append(dict(exp=exp_name, prim=prim, v=v, macs=macs,
                                 us_direct=us_d, us_engine=us_e,
                                 mcu_lat_scalar=lat_s, mcu_e_scalar=e_s,
                                 mcu_lat_simd=lat_v, mcu_e_simd=e_v))
                emit(f"fig2/{exp_name}/{prim}/{pname}={v}", us_e,
                     f"macs={macs} us_direct={us_d:.1f} "
                     f"speedup={us_d/max(us_e,1e-9):.2f} "
                     f"mcu_ms_scalar={lat_s*1e3:.2f} mcu_mJ_scalar={e_s:.3f} "
                     f"mcu_ms_simd={lat_v*1e3:.2f} mcu_mJ_simd={e_v:.3f}")

    # --- paper regression claims ------------------------------------------
    macs = [r["macs"] for r in rows]
    r2_scalar = r_squared(macs, [r["mcu_e_scalar"] for r in rows])
    r2_simd_macs = r_squared(macs, [r["mcu_e_simd"] for r in rows])
    r2_simd_lat = r_squared([r["mcu_lat_simd"] for r in rows],
                            [r["mcu_e_simd"] for r in rows])
    emit("fig2/regression/no_simd_macs_vs_energy", 0.0, f"r2={r2_scalar:.4f}")
    emit("fig2/regression/simd_macs_vs_energy", 0.0, f"r2={r2_simd_macs:.4f}")
    emit("fig2/regression/simd_latency_vs_energy", 0.0, f"r2={r2_simd_lat:.4f}")
    emit("fig2/claims", 0.0,
         f"no_simd_linear={r2_scalar > 0.99} "
         f"latency_beats_macs_with_simd={r2_simd_lat > r2_simd_macs}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
