"""Paper Table 4: compiler-optimization effect. The TPU-framework analogue:
the SAME Pallas kernel body executed (a) interpret=True (unoptimized,
python-interpreted — the -O0 stand-in) vs (b) XLA-compiled reference path
(-Os stand-in); plus the modeled MCU numbers with the paper's measured
penalty factors. Reproduces the claim that optimization matters far MORE
for the matrix-engine path (paper: 9.81x vs 1.52x)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import ConvSpec, MCUModel
from repro.kernels.conv_im2col import conv2d_im2col
from repro.kernels import ref

from .common import emit, time_fn


def main():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 32, 32, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16))

    f_interp = functools.partial(conv2d_im2col, interpret=True)   # "O0"
    f_comp = jax.jit(lambda a, b: ref.conv2d_ref(a, b))           # "Os"
    us_o0 = time_fn(f_interp, x, w, reps=3, warmup=1)
    us_os = time_fn(f_comp, x, w, reps=5, warmup=2)
    emit("table4/engine/interpret_O0", us_o0, "")
    emit("table4/engine/compiled_Os", us_os,
         f"speedup={us_o0/max(us_os,1e-9):.1f}x")

    mcu = MCUModel()
    spec = ConvSpec(primitive="standard", in_channels=3, out_channels=32,
                    kernel_size=3, use_bias=False)
    for simd in (False, True):
        tag = "simd" if simd else "no_simd"
        for opt in ("O0", "Os"):
            lat = mcu.latency_s(spec, 32, simd=simd, opt=opt)
            e = mcu.energy_mj(spec, 32, simd=simd, opt=opt)
            emit(f"table4/mcu/{tag}/{opt}", lat * 1e6,
                 f"latency_s={lat:.3f} energy_mJ={e:.2f}")
    s_ns = mcu.latency_s(spec, 32, simd=False, opt="O0") / \
        mcu.latency_s(spec, 32, simd=False, opt="Os")
    s_s = mcu.latency_s(spec, 32, simd=True, opt="O0") / \
        mcu.latency_s(spec, 32, simd=True, opt="Os")
    emit("table4/claim_opt_matters_more_with_simd", 0.0,
         f"speedup_no_simd={s_ns:.2f} speedup_simd={s_s:.2f} holds={s_s > s_ns}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
