"""Per-kernel microbench: Pallas (interpret on CPU; the TPU kernel) next to
the pure-jnp oracle, plus the int8 MXU-path variants and a tuned-vs-default
schedule comparison (repro.tune)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import tune
from repro.kernels import ref
from repro.kernels.conv_im2col import conv2d_im2col
from repro.kernels.conv_dw import depthwise2d
from repro.kernels.conv_shift import shift_conv2d
from repro.kernels.conv_add import add_conv2d
from repro.kernels.conv1d_causal import causal_conv1d
from repro.kernels.matmul_q8 import matmul

from .common import emit, time_fn

KEY = jax.random.PRNGKey(0)


def main():
    x = jax.random.normal(KEY, (1, 16, 16, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 16, 16))
    us = time_fn(functools.partial(conv2d_im2col, interpret=True), x, w,
                 reps=2, warmup=1)
    us_ref = time_fn(jax.jit(lambda a, b: ref.conv2d_ref(a, b)), x, w)
    emit("kernels/conv_im2col/pallas_interpret", us, f"ref_us={us_ref:.1f}")

    xq = (x * 20).astype(jnp.int8)
    wq = (w * 10).astype(jnp.int8)
    us_q = time_fn(functools.partial(conv2d_im2col, requant_shift=6,
                                     interpret=True), xq, wq, reps=2, warmup=1)
    emit("kernels/conv_im2col/int8", us_q, "algorithm1_epilogue")

    wd = jax.random.normal(KEY, (3, 3, 16))
    emit("kernels/conv_dw/pallas_interpret",
         time_fn(functools.partial(depthwise2d, interpret=True), x, wd,
                 reps=2, warmup=1), "")

    shifts = jnp.array([[(i % 3) - 1, ((i // 3) % 3) - 1] for i in range(16)],
                       jnp.int32)
    wp = jax.random.normal(KEY, (16, 16))
    emit("kernels/conv_shift/pallas_interpret",
         time_fn(functools.partial(shift_conv2d, interpret=True), x, shifts,
                 wp, reps=2, warmup=1), "shift_fused_into_sampling")

    emit("kernels/conv_add/pallas_interpret",
         time_fn(functools.partial(add_conv2d, interpret=True, block_co=4),
                 x, w, reps=2, warmup=1), "vpu_only_no_mxu_analogue")

    xs = jax.random.normal(KEY, (2, 128, 32))
    wc = jax.random.normal(KEY, (4, 32))
    emit("kernels/conv1d_causal/pallas_interpret",
         time_fn(functools.partial(causal_conv1d, interpret=True), xs, wc,
                 reps=2, warmup=1), "mamba_hotpath")

    a = jax.random.normal(KEY, (256, 256), jnp.bfloat16)
    b = jax.random.normal(KEY, (256, 256), jnp.bfloat16)
    emit("kernels/matmul/pallas_interpret",
         time_fn(functools.partial(matmul, bm=128, bn=128, bk=128,
                                   interpret=True), a, b, reps=2, warmup=1), "")
    aq = (jax.random.normal(KEY, (256, 256)) * 30).astype(jnp.int8)
    emit("kernels/matmul_q8/pallas_interpret",
         time_fn(functools.partial(matmul, bm=128, bn=128, bk=128,
                                   requant_shift=7, interpret=True), aq, aq,
                 reps=2, warmup=1), "int8_pow2_requant")

    tuned_vs_default()


def tuned_vs_default():
    """Autotune a few representative shapes in-process and report how the
    measured winner compares to the hard-coded default schedule (the cache
    committed by scripts/tune.py makes these wins transparent at dispatch)."""
    xw = jax.random.normal(KEY, (1, 10, 10, 128))
    ww = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 128, 64))
    xa = jax.random.normal(KEY, (1, 16, 16, 16))
    wa = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 16, 16))
    a = jax.random.normal(KEY, (512, 512), jnp.bfloat16)
    combos = [
        # wide-channel conv: filter-block size trades weight reuse vs steps
        ("conv2d", tune.sig_conv2d(1, 10, 10, 128, 64, 3), (xw, ww)),
        # VPU add-conv: the |a-b| broadcast intermediate scales with block_co
        ("add_conv2d", tune.sig_add_conv2d(1, 16, 16, 16, 16, 3), (xa, wa)),
        # 512^3 matmul: the default 256x256 output blocking runs 4 grid
        # steps where a 512-wide block runs 1 — a real schedule gap
        ("matmul", tune.sig_matmul(512, 512, 512), (a, a)),
    ]
    for kernel, sig, args in combos:
        best, best_us, results = tune.autotune(kernel, sig, args,
                                               reps=3, warmup=1)
        default_us = next(us for cfg, us in results
                          if cfg == tune.default_config(kernel))
        emit(f"kernels/tune/{kernel}/{sig.key()}", best_us,
             f"default_us={default_us:.1f} best={best} "
             f"speedup={default_us / max(best_us, 1e-9):.2f} "
             f"tuned_beats_default={best_us < default_us}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
