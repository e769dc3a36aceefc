"""Integer-only quantized forward paths for all five primitives.

Mirrors NNoM's execution model: int8 operands, int32 accumulation, one
arithmetic shift to the output scale (Algorithm 1), optional bias added at
accumulator scale. BN is folded beforehand for the multiplicative
primitives (folding.fold); add-conv keeps an explicit integer BN-free path
followed by a float BN (the paper's layout).

Dispatch: every primitive routes through the kernel layer
(``repro.kernels.ops``), so the quantized network runs the SAME schedules
(and the same ``repro.tune`` autotuned configs) as the float one:

* ``method="pallas"`` — the TPU kernels with their fused int8 epilogues,
  the analogue of the paper's CMSIS-NN/SIMD build;
* ``method="xla"`` — the pure-jnp integer oracles (``kernels.ref``), the
  direct / no-SIMD baseline.

Both methods accumulate in int32 and share ``kernels.common.apply_requant``,
so they are bit-exact against each other (tests/test_qconv.py). Layers the
kernel layer cannot express (stride != 1 or non-SAME padding) fall back to
a raw ``lax`` integer path under ``method="xla"`` and raise under
``method="pallas"``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .primitives import ConvSpec, int8_conv, shift_channels, _DN
from .quantize import (QTensor, QTensorW4, addmac_align, quantize_w4,
                       requantize, rshift_round)


def _conv_int(x_q: jax.Array, w_q: jax.Array, *, stride=1, padding="SAME",
              groups=1) -> jax.Array:
    """int8 x int8 -> int32 convolution (the MXU-native contraction)."""
    return int8_conv(x_q, w_q, stride=stride, padding=padding, groups=groups)


def _bias_acc(bias: Optional[QTensor], acc_fb: int) -> Optional[jax.Array]:
    """Bias rescaled to the int32 accumulator scale (Algorithm 1, line 2)."""
    if bias is None:
        return None
    return rshift_round(bias.q.astype(jnp.int32), bias.frac_bits - acc_fb)


def _add_preshifts(fb_x: int, fb_w: int):
    """Algorithm 1 (right) scale alignment: left-shift the coarser operand
    onto the finer scale; the accumulator then carries max(fb_x, fb_w)
    fractional bits (same arithmetic as quantize.addmac_align, but as static
    per-layer shifts the kernels can fuse)."""
    if fb_x > fb_w:
        return 0, fb_x - fb_w, fb_x
    if fb_w > fb_x:
        return fb_w - fb_x, 0, fb_w
    return 0, 0, fb_x


def _kernel_layer_ok(spec: ConvSpec) -> bool:
    return spec.stride == 1 and spec.padding == "SAME"


def _wq(w):
    """(weight array, w_shifts-or-None) for the kernel layer: a QTensorW4
    leaf stays nibble-packed (the kernels unpack in-register); a QTensor
    passes through. Scale math is identical either way — ``frac_bits`` is
    the W4 *base* scale, and the expanded codes live at exactly that
    scale."""
    if isinstance(w, QTensorW4):
        return w.q, w.shifts
    return w.q, None


def _expand_w4_qparams(qparams: dict) -> dict:
    """W4 leaves -> equivalent int8 QTensors (for the raw-lax fallback path,
    which has no packed-weight kernels)."""
    out = {}
    for k, v in qparams.items():
        if isinstance(v, QTensorW4):
            out[k] = QTensor(v.expand(), v.frac_bits)
        else:
            out[k] = v
    return out


def qconv_apply(qparams: dict, x: QTensor, spec: ConvSpec, out_frac_bits: int,
                *, method: str = "xla", act: Optional[str] = None,
                configs: Optional[dict] = None) -> QTensor:
    """Run one quantized primitive layer; returns int8 QTensor.

    ``method`` picks the execution engine in the kernel layer: ``"pallas"``
    (TPU kernels, fused requantization) or ``"xla"`` (jnp integer oracle).
    ``act="relu"`` fuses the activation into the layer's LAST kernel stage
    at accumulator scale (the graph executor's fused conv+BN+ReLU block).
    ``configs`` pins Pallas schedules per stage — ``{"main": {...}}`` for the
    single-kernel primitives, ``{"dw": ..., "pw": ...}`` for dws; only legal
    with ``method="pallas"`` (the oracle has no schedule knobs).
    """
    from repro.kernels import ops as K   # lazy: core must import without kernels

    if method not in ("pallas", "xla"):
        raise ValueError(f"unknown method {method!r}; expected 'pallas' or 'xla'")
    if configs is not None and method != "pallas":
        raise ValueError("qconv_apply: configs= pins Pallas schedules; "
                         "method='xla' has none (drop configs or use pallas)")
    p = spec.primitive
    bias = qparams.get("b")
    cfgs = configs or {}

    if not _kernel_layer_ok(spec):
        if method == "pallas":
            raise NotImplementedError(
                f"qconv_apply(method='pallas'): the Pallas kernel layer only "
                f"supports stride=1 SAME layers, got stride={spec.stride} "
                f"padding={spec.padding!r}; use method='xla'")
        return _qconv_apply_lax(_expand_w4_qparams(qparams), x, spec,
                                out_frac_bits, act=act)

    if p in ("standard", "grouped"):
        w = qparams["w"]
        wq, ws = _wq(w)
        groups = spec.groups if p == "grouped" else 1
        acc_fb = x.frac_bits + w.frac_bits
        y = K.conv2d(x.q, wq, _bias_acc(bias, acc_fb), groups=groups,
                     method=method, requant_shift=acc_fb - out_frac_bits,
                     act=act, config=cfgs.get("main"), w_shifts=ws)
        return QTensor(y, out_frac_bits)

    if p == "dws":
        w_dw, w_pw = qparams["w_dw"], qparams["w_pw"]
        wdq, wds = _wq(w_dw)
        wpq, wps = _wq(w_pw)
        # depthwise at an intermediate scale, then pointwise
        mid_fb = qparams.get("mid_frac_bits", out_frac_bits)
        h = K.depthwise2d(x.q, wdq, method=method,
                          requant_shift=x.frac_bits + w_dw.frac_bits - mid_fb,
                          config=cfgs.get("dw"), w_shifts=wds)
        acc_fb = mid_fb + w_pw.frac_bits
        y = K.conv2d(h, wpq, _bias_acc(bias, acc_fb), method=method,
                     requant_shift=acc_fb - out_frac_bits, act=act,
                     config=cfgs.get("pw"), w_shifts=wps)
        return QTensor(y, out_frac_bits)

    if p == "shift":
        # shift is pure data movement: exact in integer domain (paper's
        # point) — the Pallas kernel fuses it into the pointwise matmul
        w_pw = qparams["w_pw"]
        wpq, wps = _wq(w_pw)
        acc_fb = x.frac_bits + w_pw.frac_bits
        y = K.shift_conv2d(x.q, qparams["shifts"], wpq,
                           _bias_acc(bias, acc_fb), method=method,
                           requant_shift=acc_fb - out_frac_bits, act=act,
                           max_shift=spec.kernel_size // 2,
                           config=cfgs.get("main"), w_shifts=wps)
        return QTensor(y, out_frac_bits)

    if p == "add":
        w = qparams["w"]
        wq, ws = _wq(w)
        x_pre, w_pre, acc_fb = _add_preshifts(x.frac_bits, w.frac_bits)
        y = K.add_conv2d(x.q, wq, _bias_acc(bias, acc_fb), method=method,
                         requant_shift=acc_fb - out_frac_bits,
                         x_preshift=x_pre, w_preshift=w_pre, act=act,
                         config=cfgs.get("main"), w_shifts=ws)
        return QTensor(y, out_frac_bits)

    raise ValueError(p)


def _qconv_apply_lax(qparams: dict, x: QTensor, spec: ConvSpec,
                     out_frac_bits: int, act: Optional[str] = None) -> QTensor:
    """Raw-lax integer path for layer shapes outside the kernel layer's
    stride-1/SAME envelope — all five primitives, same Algorithm-1
    arithmetic as the ops dispatch (int32 accumulation, accumulator-scale
    bias, fused act, round-to-nearest requantization)."""
    from repro.kernels.common import apply_act

    p = spec.primitive
    bias = qparams.get("b")

    def finish(acc, acc_fb):
        b_acc = _bias_acc(bias, acc_fb)
        if b_acc is not None:
            acc = acc + b_acc
        acc = apply_act(acc, act)
        return QTensor(requantize(acc, acc_fb, out_frac_bits), out_frac_bits)

    if p in ("standard", "grouped"):
        w = qparams["w"]
        groups = spec.groups if p == "grouped" else 1
        acc = _conv_int(x.q, w.q, stride=spec.stride, padding=spec.padding,
                        groups=groups)
        return finish(acc, x.frac_bits + w.frac_bits)

    if p == "dws":
        w_dw, w_pw = qparams["w_dw"], qparams["w_pw"]
        mid_fb = qparams.get("mid_frac_bits", out_frac_bits)
        acc = _conv_int(x.q, jnp.transpose(w_dw.q, (0, 1, 3, 2)),
                        stride=spec.stride, padding=spec.padding,
                        groups=spec.in_channels)
        h = requantize(acc, x.frac_bits + w_dw.frac_bits, mid_fb)
        acc2 = _conv_int(h, w_pw.q, stride=1, padding="SAME")
        return finish(acc2, mid_fb + w_pw.frac_bits)

    if p == "shift":
        w_pw = qparams["w_pw"]
        shifted = shift_channels(x.q, qparams["shifts"],
                                 max_shift=spec.kernel_size // 2)
        acc = _conv_int(shifted, w_pw.q, stride=spec.stride, padding="SAME")
        return finish(acc, x.frac_bits + w_pw.frac_bits)

    if p == "add":
        w = qparams["w"]
        hk, cx = spec.kernel_size, spec.in_channels
        pads = ((hk // 2, (hk - 1) // 2),) * 2 if spec.padding == "SAME" \
            else ((0, 0), (0, 0))
        patches = lax.conv_general_dilated_patches(
            x.q.astype(jnp.int32), (hk, hk), (1, 1), pads,
            dimension_numbers=_DN)
        b, hy, wy, _ = patches.shape
        patches = patches.reshape(b, hy, wy, cx, hk * hk)
        wk = jnp.transpose(w.q, (2, 0, 1, 3)) \
            .reshape(cx, hk * hk, spec.out_channels).astype(jnp.int32)
        xi, wi, acc_fb = addmac_align(patches[..., None], wk[None, None, None],
                                      x.frac_bits, w.frac_bits)
        acc = -jnp.sum(jnp.abs(xi - wi), axis=(3, 4))
        return finish(acc, acc_fb)

    raise ValueError(p)


def _w4_axis(key: str, v) -> int:
    """W4 packing axis per parameter key: the axis the kernels unpack along
    — always one the grid does NOT block (input channels for the
    matmul-family weights — ``ndim - 2`` so 2D pointwise layouts work too —
    tap rows for depthwise, so channels keep the 128-lane axis)."""
    return 0 if key == "w_dw" else v.ndim - 2


def quantize_conv_params(params: dict, spec: ConvSpec, *, bits: int = 8,
                         group_size: int = 32) -> dict:
    """Power-of-two PTQ of a float primitive layer.

    ``bits=8`` (default): per-tensor int8 QTensors, as before. ``bits=4``:
    weight tensors become nibble-packed :class:`QTensorW4` with per-group
    scales (``group_size`` consecutive elements along the unpack axis);
    biases stay int8 (they are added at int32 accumulator scale, packing
    them buys nothing). ``qconv_apply`` routes W4 leaves to the packed
    kernel paths; the raw-lax fallback expands them first.
    """
    from .quantize import quantize
    if bits not in (8, 4):
        raise ValueError(f"quantize_conv_params: bits must be 8 or 4, "
                         f"got {bits}")
    out = {}
    for k, v in params.items():
        if k == "shifts":
            out[k] = v
        elif bits == 4 and k in ("w", "w_dw", "w_pw"):
            out[k] = quantize_w4(v, axis=_w4_axis(k, v),
                                 group_size=group_size)
        else:
            out[k] = quantize(v)
    return out
