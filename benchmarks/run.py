# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

  table1  -> primitive_costs   (params/MACs formulas)
  fig2    -> sweeps            (latency/energy vs structural params, r^2 claims)
  fig3    -> memaccess         (data-reuse ratio)
  table3  -> frequency         (MCU frequency/power/energy model)
  table4  -> optlevel          (interpret vs compiled; O0 vs Os)
  kernels -> kernel microbench (Pallas interpret vs jnp oracle)
  quant   -> quant_bench       (pallas-int8 / xla-int8 / float per primitive)
  layers  -> layer_bench       (repro.graph per-layer breakdown; fused vs
                                unfused float-bounce e2e)
  throughput -> throughput_bench (batched CompiledPlan images/s vs the N=1
                                loop; MACs/byte reuse table; CNNEngine)
  roofline-> roofline_report   (from dry-run artifacts, if present)
  serving -> serve_bench       (static-drain vs continuous batching)

Section-by-section expected output shapes are documented in
EXPERIMENTS.md. REPRO_BENCH_FAST=1 trims sweep points for CI.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from . import (frequency, kernels_bench, layer_bench, memaccess, optlevel,
                   primitive_costs, quant_bench, roofline_report, serve_bench,
                   sweeps, throughput_bench)
    sections = [
        ("table1", primitive_costs.main),
        ("fig2", sweeps.main),
        ("fig3", memaccess.main),
        ("table3", frequency.main),
        ("table4", optlevel.main),
        ("kernels", kernels_bench.main),
        ("quant", quant_bench.main),
        ("layers", layer_bench.main),
        ("throughput", throughput_bench.main),
        ("roofline", roofline_report.main),
        ("serving", serve_bench.main),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in sections:
        try:
            fn()
        except Exception as e:      # noqa: BLE001 — report, keep benching
            failures += 1
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
    print(f"done,0.0,sections={len(sections)} failures={failures}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
