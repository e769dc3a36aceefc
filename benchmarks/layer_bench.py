"""Per-layer cost attribution through repro.graph (EXPERIMENTS.md §Per-layer).

The paper reads its Table 2 per LAYER, not per network — "Not All Ops Are
Created Equal!" is the motivating citation — so this section lowers one CNN
per primitive and emits the plan's per-node breakdown from
``CompiledPlan.profile``: measured latency, analytic MACs, and the
paper-calibrated MCU latency/energy model (scalar vs SIMD, 84 MHz).

It then times the same plan end to end twice:

  * **fused**     — the single-jit integer executor (int8 activations
    through ReLU+pool, requantization chained into the kernel epilogues);
  * **unfused**   — ``repro.graph.unfused_forward``: the pre-graph
    float-bounce regime (dequantize -> float ReLU/pool -> requantize per
    block) at the same scales, also jitted end to end.

Both run the same integer conv arithmetic and are bit-exact (reported as
``exact=``); fused does strictly less work, so ``fused_us <= unfused_us``
is the expected shape of the result.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import Primitives
from repro.graph import CompiledPlan, build_cnn_graph, lower, unfused_forward
from repro.models.convnet import CNNConfig, init_cnn

from .common import FAST, emit, time_fn


def _paired_time(fn_a, fn_b, x, *, rounds: int = 11) -> tuple:
    """Median microseconds for two jitted fns, measured in interleaved
    A/B rounds so slow drift in background load hits both sides equally —
    the e2e fused-vs-unfused delta is the claim under test, so it must not
    be an artifact of when each side happened to run."""
    import time

    import numpy as np
    jax.block_until_ready(fn_a(x))
    jax.block_until_ready(fn_b(x))
    ta, tb = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a(x))
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b(x))
        tb.append(time.perf_counter() - t0)
    return float(np.median(ta) * 1e6), float(np.median(tb) * 1e6)


def _cfg(prim: str) -> CNNConfig:
    if FAST:
        return CNNConfig(primitive=prim, widths=(8, 12), image_size=16)
    return CNNConfig(primitive=prim, widths=(16, 32, 64), image_size=32)


def main() -> None:
    batch = 2 if FAST else 4
    for prim in Primitives:
        cfg = _cfg(prim)
        key = jax.random.PRNGKey(0)
        params = init_cnn(cfg, key)
        calib = jax.random.normal(jax.random.PRNGKey(1),
                                  (batch, cfg.image_size, cfg.image_size,
                                   cfg.in_channels)) * 0.5
        x = jax.random.normal(jax.random.PRNGKey(2), calib.shape) * 0.5

        plan = lower(build_cnn_graph(cfg), params, calib)
        ex = CompiledPlan(plan, method="auto")

        total_macs = sum(n.spec.mac_count(n.attrs["in_hw"][1])
                         for n in plan.conv_nodes())
        for row in ex.profile(x):
            derived = f"op={row['op']};macs={row['macs']}"
            if row["op"] == "qconv":
                derived += (f";mac_share={row['macs'] / total_macs:.3f}"
                            f";mcu_lat_scalar_ms={row['mcu_lat_scalar_ms']:.3f}"
                            f";mcu_lat_simd_ms={row['mcu_lat_simd_ms']:.3f}"
                            f";mcu_e_scalar_mj={row['mcu_e_scalar_mj']:.4f}"
                            f";mcu_e_simd_mj={row['mcu_e_simd_mj']:.4f}")
            emit(f"layers/{prim}/{row['name']}", row["us"], derived)

        # e2e comparison runs both regimes on the SAME engine (the oracle:
        # fast everywhere, incl. interpret-mode CI) so the delta isolates
        # the fusion, not pallas-vs-xla; a serving-sized batch keeps the
        # removed per-block float bounce above timing noise
        xl = jax.random.normal(jax.random.PRNGKey(3),
                               (16 if FAST else 32,) + x.shape[1:]) * 0.5
        fused = CompiledPlan(plan, method="xla")._fn
        unfused = jax.jit(lambda v: unfused_forward(plan, v, method="xla"))
        exact = int(bool(jnp.all(jnp.isclose(fused(xl), unfused(xl),
                                             rtol=1e-6, atol=1e-6))))
        if not exact:    # run.py reports this as a section failure
            raise RuntimeError(
                f"layers/{prim}: fused executor diverged from the unfused "
                "float-bounce reference — the fusion pass is no longer exact")
        fused_us, unfused_us = _paired_time(fused, unfused, xl)
        emit(f"layers/{prim}/e2e", fused_us,
             f"unfused_us={unfused_us:.1f};"
             f"fused_over_unfused={fused_us / max(unfused_us, 1e-9):.3f};"
             f"exact={exact}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    main()
