"""Pure-jnp oracles for every Pallas kernel (the 'no-SIMD' reference path).

These double as (a) allclose targets for the kernel tests and (b) the
scalar/direct baseline in the benchmark harness — the analogue of the
paper's non-SIMD NNoM implementations.

The ``*_q8_ref`` variants are the integer-only oracles: int8 operands,
int32 accumulation, and the SAME Algorithm-1 epilogue as the Pallas kernels
(``common.apply_requant`` — round-to-nearest shift, clip, int8; with the
optional ``act="relu"`` fused at accumulator scale via ``common.apply_act``
first, exactly like the kernel epilogues). Integer accumulation is
order-independent, so the Pallas kernels are bit-exact against these refs,
which is what ``tests/test_qconv.py`` and ``tests/test_graph.py`` assert.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import primitives as P

from .common import apply_act, apply_requant


def conv2d_ref(x, w, bias=None, *, groups: int = 1, act=None):
    y = P.standard_conv(x, w, groups=groups)
    if bias is not None:
        y = y + bias
    return apply_act(y, act)


def conv2d_q8_ref(x_q, w_q, bias_q=None, *, groups: int = 1,
                  requant_shift: int = 0, act=None):
    acc = P.int8_conv(x_q, w_q, groups=groups)
    if bias_q is not None:
        acc = acc + bias_q.astype(jnp.int32)
    acc = apply_act(acc, act)
    return apply_requant(acc, requant_shift).astype(jnp.int8)


def depthwise2d_ref(x, w_dw, *, act=None):
    w4 = w_dw[..., None] if w_dw.ndim == 3 else w_dw   # (HK,HK,C) -> (HK,HK,C,1)
    return apply_act(P.depthwise_conv(x, w4), act)


def depthwise2d_q8_ref(x_q, w_dw_q, *, requant_shift: int = 0, act=None):
    # per-tap int32 multiply-accumulate: exact on every backend (XLA:TPU
    # miscomputes grouped integer convolutions, see P.int8_conv)
    w = w_dw_q[..., 0] if w_dw_q.ndim == 4 else w_dw_q
    hk = w.shape[0]
    h, wd = x_q.shape[1:3]
    lo = (hk - 1) // 2                      # XLA's SAME split of hk - 1
    xp = jnp.pad(x_q.astype(jnp.int32),
                 ((0, 0), (lo, hk - 1 - lo), (lo, hk - 1 - lo), (0, 0)))
    acc = sum(xp[:, i:i + h, j:j + wd, :] * w[i, j].astype(jnp.int32)
              for i in range(hk) for j in range(hk))
    acc = apply_act(acc, act)
    return apply_requant(acc, requant_shift).astype(jnp.int8)


def shift_conv2d_ref(x, shifts, w_pw, *, max_shift=None, act=None):
    w4 = w_pw[None, None] if w_pw.ndim == 2 else w_pw
    return apply_act(P.standard_conv(
        P.shift_channels(x, jnp.asarray(shifts), max_shift=max_shift), w4), act)


def shift_conv2d_q8_ref(x_q, shifts, w_pw_q, bias_q=None, *,
                        requant_shift: int = 0, max_shift=None, act=None):
    """Shift is pure data movement — exact in the integer domain (the paper's
    point) — so only the pointwise matmul accumulates."""
    w4 = w_pw_q[None, None] if w_pw_q.ndim == 2 else w_pw_q
    shifted = P.shift_channels(x_q, jnp.asarray(shifts), max_shift=max_shift)
    acc = P.int8_conv(shifted, w4)
    if bias_q is not None:
        acc = acc + bias_q.astype(jnp.int32)
    acc = apply_act(acc, act)
    return apply_requant(acc, requant_shift).astype(jnp.int8)


def add_conv2d_ref(x, w, *, act=None):
    return apply_act(P.add_conv(x, w), act)


def add_conv2d_q8_ref(x_q, w_q, bias_q=None, *, requant_shift: int = 0,
                      x_preshift: int = 0, w_preshift: int = 0, act=None):
    """AdderNet Algorithm-1 (right): align scales by left pre-shifts, then
    -Σ|x - w| in int32, bias at accumulator scale, requant epilogue."""
    xi = x_q.astype(jnp.int32)
    wi = w_q.astype(jnp.int32)
    if x_preshift:
        xi = jnp.left_shift(xi, x_preshift)
    if w_preshift:
        wi = jnp.left_shift(wi, w_preshift)
    acc = P.add_conv(xi, wi)
    if bias_q is not None:
        acc = acc + bias_q.astype(jnp.int32)
    acc = apply_act(acc, act)
    return apply_requant(acc, requant_shift).astype(jnp.int8)


# --------------------------------------------------------------------------
# W4 oracles: expand the nibble-packed weights to their int8 codes on the
# host (``core.quantize.expand_w4`` — unpack + per-group shift to the base
# scale), then run the UNCHANGED int8 oracle. This is the contract every W4
# Pallas kernel is tested bit-exact against: pallas == xla == oracle.
# --------------------------------------------------------------------------

def _w4_codes(w_p, w_shifts, size: int, axis: int):
    from repro.core.quantize import expand_w4
    return expand_w4(w_p, w_shifts, size, axis)


def conv2d_w4_ref(x_q, w_p, w_shifts, bias_q=None, *, groups: int = 1,
                  requant_shift: int = 0, act=None):
    cxg = x_q.shape[-1] // groups
    return conv2d_q8_ref(x_q, _w4_codes(w_p, w_shifts, cxg, 2), bias_q,
                         groups=groups, requant_shift=requant_shift, act=act)


def depthwise2d_w4_ref(x_q, w_dw_p, w_shifts, *, requant_shift: int = 0,
                       act=None):
    if w_dw_p.ndim == 4:
        w_dw_p = w_dw_p[..., 0]
    hk = w_dw_p.shape[1]                     # axis 0 is the packed tap axis
    return depthwise2d_q8_ref(x_q, _w4_codes(w_dw_p, w_shifts, hk, 0),
                              requant_shift=requant_shift, act=act)


def shift_conv2d_w4_ref(x_q, shifts, w_pw_p, w_shifts, bias_q=None, *,
                        requant_shift: int = 0, max_shift=None, act=None):
    if w_pw_p.ndim == 4:
        w_pw_p = w_pw_p[0, 0]
    c = x_q.shape[-1]
    return shift_conv2d_q8_ref(x_q, shifts, _w4_codes(w_pw_p, w_shifts, c, 0),
                               bias_q, requant_shift=requant_shift,
                               max_shift=max_shift, act=act)


def add_conv2d_w4_ref(x_q, w_p, w_shifts, bias_q=None, *,
                      requant_shift: int = 0, x_preshift: int = 0,
                      w_preshift: int = 0, act=None):
    cx = x_q.shape[-1]
    return add_conv2d_q8_ref(x_q, _w4_codes(w_p, w_shifts, cx, 2), bias_q,
                             requant_shift=requant_shift,
                             x_preshift=x_preshift, w_preshift=w_preshift,
                             act=act)


def matmul_w4_ref(a, b_p, w_shifts, *, requant_shift, act=None):
    k = a.shape[-1]
    return matmul_ref(a, _w4_codes(b_p, w_shifts, k, 0),
                      requant_shift=requant_shift, act=act)


def causal_conv1d_ref(x, w, *, act=None):
    """x: (B,L,D); w: (K,D). Zero history before t=0."""
    if w.ndim == 3:
        w = w[:, 0]
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for kk in range(k):
        out = out + xp[:, kk:kk + x.shape[1], :] * w[kk][None, None, :]
    return apply_act(out, act)


def matmul_ref(a, b, *, requant_shift=None, act=None):
    if requant_shift is None:
        return apply_act(jnp.dot(a, b, preferred_element_type=jnp.float32),
                         act).astype(a.dtype)
    acc = jnp.dot(a.astype(jnp.int8), b.astype(jnp.int8),
                  preferred_element_type=jnp.int32)
    acc = apply_act(acc, act)
    return apply_requant(acc, requant_shift).astype(jnp.int8)


def maxpool2d_ref(x, *, window: int = 2, stride: int | None = None):
    """VALID max-pool oracle — works on int8 codes (init = dtype min) and
    floats (init = -inf) alike."""
    stride = stride or window
    if jnp.issubdtype(x.dtype, jnp.integer):
        init = jnp.iinfo(x.dtype).min
    else:
        init = -jnp.inf
    return lax.reduce_window(x, jnp.asarray(init, x.dtype), lax.max,
                             (1, window, window, 1), (1, stride, stride, 1),
                             "VALID")
