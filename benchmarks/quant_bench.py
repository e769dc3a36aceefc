"""Paper Tables 3-4 quantized counterpart: integer-only latency + energy per
primitive (see EXPERIMENTS.md §Quantized and §Sub-byte).

Three engines per Table-2 sweep shape, all running the SAME Algorithm-1
arithmetic where quantized:

  * pallas-int8 — ``qconv_apply(method="pallas")``: fused int8 kernels with
    shift-requantized epilogues, the TPU analogue of the paper's CMSIS-NN
    SIMD build (Table 4's "with SIMD" column);
  * xla-int8    — ``qconv_apply(method="xla")``: the jnp integer oracle,
    the direct / no-SIMD baseline (bit-exact with pallas-int8 — asserted
    per row and reported as ``exact=``);
  * float       — the float reference primitive.

Each shape also gets a ``quant_w4/...`` row: the same layer with its
weights nibble-packed to W4 (``quantize_conv_params(bits=4)``, two int4
codes per byte + per-group shift scales). ``exact=`` there asserts the
triple contract pallas == xla == expanded-int8 oracle (packing changes
data movement, never arithmetic) and the ``w*_wbytes`` fields report the
weight bytes a decode step moves — W4 must be ~half of W8 modulo the
group-shift sideband (``±`` packing overhead).

``derived`` also carries the paper-side model quantities from
``core/energy.py`` (MCU @ 84 MHz, constants calibrated to paper Table 3):
theoretical MACs, modeled scalar vs SIMD energy (mJ) and their ratio —
the MACs<->energy linearity the paper validates holds per construction for
the scalar column; the SIMD column tracks data movement instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ConvSpec, MCUModel, apply, init
from repro.core.qconv import qconv_apply, quantize_conv_params
from repro.core.quantize import QTensor, QTensorW4, frac_bits_for, quantize

from .common import FAST, emit, time_fn

# Table-2 sweep points: the center cell per primitive plus the structural
# extremes the paper sweeps (groups / kernel / cin). FAST trims to the five
# center cells at a smaller width.
def _shapes():
    w = 16 if FAST else 32
    pts = [
        ("standard", ConvSpec("standard", 16, 16, 3), w),
        ("grouped", ConvSpec("grouped", 16, 16, 3, groups=2), w),
        ("dws", ConvSpec("dws", 16, 16, 3), w),
        ("shift", ConvSpec("shift", 16, 16, 3), w),
        ("add", ConvSpec("add", 16, 16, 3), 8 if FAST else 10),
    ]
    if not FAST:
        pts += [
            ("standard_cin128", ConvSpec("standard", 128, 64, 3), 10),
            ("grouped_g4", ConvSpec("grouped", 128, 64, 3, groups=4), 10),
            ("standard_k7", ConvSpec("standard", 16, 16, 7), w),
        ]
    return pts


def main() -> None:
    mcu = MCUModel()
    key = jax.random.PRNGKey(0)
    for name, spec, width in _shapes():
        params = init(key, spec)
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (1, width, width, spec.in_channels)) * 0.5

        f_float = jax.jit(lambda xx, p=params, s=spec: apply(p, xx, s))
        float_us = time_fn(f_float, x)

        yf = f_float(x)
        ofb = frac_bits_for(yf)
        qp = quantize_conv_params(params, spec)
        xq = quantize(x)

        def int_fn(method):
            fb = xq.frac_bits
            return jax.jit(lambda q, m=method, s=spec, o=ofb, qq=qp:
                           qconv_apply(qq, QTensor(q, fb), s, o, method=m).q)

        f_pallas, f_xla = int_fn("pallas"), int_fn("xla")
        exact = int(bool(jnp.all(f_pallas(xq.q) == f_xla(xq.q))))
        if not exact:   # the run.py harness reports this as a section failure
            raise RuntimeError(
                f"quant/{name}: pallas-int8 diverged from xla-int8 — the "
                "shared apply_requant epilogue contract is broken")
        pallas_us = time_fn(f_pallas, xq.q)
        xla_us = time_fn(f_xla, xq.q)

        macs = spec.mac_count(width)
        e_scalar = mcu.energy_mj(spec, width, simd=False)
        e_simd = mcu.energy_mj(spec, width, simd=True)
        emit(f"quant/{name}/w={width}", pallas_us,
             f"xla_int8_us={xla_us:.1f};float_us={float_us:.1f};"
             f"exact={exact};macs={macs};"
             f"mcu_e_scalar_mj={e_scalar:.3f};mcu_e_simd_mj={e_simd:.3f};"
             f"mcu_e_ratio={e_scalar / max(e_simd, 1e-12):.2f}")

        # ---- W4A8 row: same layer, nibble-packed weights -----------------
        qp4 = quantize_conv_params(params, spec, bits=4)
        qp4x = {k: QTensor(v.expand(), v.frac_bits)
                if isinstance(v, QTensorW4) else v for k, v in qp4.items()}

        def w4_fn(method, qq):
            fb = xq.frac_bits
            return jax.jit(lambda q, m=method, s=spec, o=ofb, p=qq:
                           qconv_apply(p, QTensor(q, fb), s, o, method=m).q)

        f4_pallas, f4_xla = w4_fn("pallas", qp4), w4_fn("xla", qp4)
        f4_oracle = w4_fn("pallas", qp4x)       # unpacked-int8 oracle codes
        y4 = f4_pallas(xq.q)
        exact4 = int(bool(jnp.all(y4 == f4_xla(xq.q))
                          & jnp.all(y4 == f4_oracle(xq.q))))
        if not exact4:
            raise RuntimeError(
                f"quant_w4/{name}: W4 path diverged from the unpacked-int8 "
                "oracle — the in-register unpack changed arithmetic")
        w4_us = time_fn(f4_pallas, xq.q)
        # weight bytes one forward moves: packed nibbles + shift sideband
        # vs the int8 codes (biases identical, excluded from both)
        w8b = sum(v.q.size for k, v in qp.items()
                  if k.startswith("w") and isinstance(v, QTensor))
        w4b = sum(v.q.size + v.shifts.size for v in qp4.values()
                  if isinstance(v, QTensorW4))
        emit(f"quant_w4/{name}/w={width}", w4_us,
             f"int8_us={pallas_us:.1f};exact={exact4};"
             f"w8_wbytes={w8b};w4_wbytes={w4b};"
             f"wbytes_ratio={w4b / max(w8b, 1):.2f};"
             f"mcu_e_simd_mj={e_simd:.3f};macs={macs}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    main()
