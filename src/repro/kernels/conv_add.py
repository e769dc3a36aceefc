"""Add (AdderNet) convolution Pallas kernel — VPU only, by necessity.

The paper could not give add-conv a SIMD path because no __SMLAD-like
instruction exists for |a-b| accumulation (§3.3). The same holds on TPU:
the MXU computes contractions (sum of products), and L1 distance
-Σ|w - x| is not a contraction, so the systolic array is unusable. This
kernel is the TPU-faithful equivalent: broadcast |patch - w| tiles on the
8x128 VPU with VMEM-blocked filters, accumulating in int32/f32. Its
per-MAC cost is intrinsically higher than the MXU paths — reproducing the
paper's measured add-conv penalty at the architectural level.

Grid: (batch_block, spatial_tile, out-channel-block). The broadcast
intermediate is (BN*BH*BW, Cx, BCO), so the spatial tile is the knob that
keeps this kernel inside VMEM; ``block_n`` amortizes filter loads like the
MXU kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (acc_dtype, apply_act, apply_requant,
                     batch_spatial_schedule, effective_block, halo_tiles,
                     resolve_interpret, resolve_tile_config, w4_plane_order,
                     w4_planes)


def _kernel(x_ref, w_ref, o_ref, *, hk, bh, bw, out_dtype, requant_shift,
            x_preshift, w_preshift, act=None, bias_ref=None, ws_ref=None):
    # x_ref: (BN, 1, 1, BH+HK-1, BW+HK-1, Cx); w_ref: (HK, HK, Cx, BCO)
    # (W4: (HK, HK, ceil(Cx/2), BCO) nibble-packed + ws_ref (Cx,) shifts;
    # x and the shifts arrive in w4_plane_order. The unpack drops the packed
    # tail element before |x - w| — a zero-padded weight channel is NOT
    # neutral for L1 distance.)
    adt = acc_dtype(x_ref.dtype)
    cx = x_ref.shape[-1]
    bco = w_ref.shape[-1]
    bn = x_ref.shape[0]
    acc = jnp.zeros((bn * bh * bw, bco), adt)
    if ws_ref is not None:
        ws = ws_ref[...].astype(jnp.int32).reshape(cx, 1)
    for i in range(hk):
        for j in range(hk):
            patch = x_ref[:, 0, 0, i:i + bh, j:j + bw, :].astype(adt)
            if x_preshift:                  # Algorithm 1 (right): align scales
                patch = jnp.left_shift(patch, x_preshift)
            if ws_ref is None:
                wv = w_ref[i, j].astype(adt)    # (Cx, BCO)
            else:                           # group shifts first (to the base
                wv = jnp.left_shift(        # scale), then the common align
                    w4_planes(w_ref[i, j], cx), ws).astype(adt)
            if w_preshift:
                wv = jnp.left_shift(wv, w_preshift)
            a = patch.reshape(bn * bh * bw, cx)
            # -Σ_c |a[:, c] - w[c, n]| : VPU broadcast, no MXU analogue
            acc = acc - jnp.sum(jnp.abs(a[:, :, None] - wv[None, :, :]), axis=1)
    if bias_ref is not None:                # bias at accumulator scale
        acc = acc + bias_ref[...].astype(adt)[None, :]
    acc = apply_act(acc, act)
    acc = apply_requant(acc, requant_shift)
    o_ref[...] = acc.reshape(bn, bh, bw, bco).astype(out_dtype)


def add_conv2d(x: jax.Array, w: jax.Array, bias=None, *, block_co: int = 128,
               block_n: int = 1, block_h: int | None = None,
               block_w: int | None = None,
               requant_shift: int | None = None, x_preshift: int = 0,
               w_preshift: int = 0, act: str | None = None, out_dtype=None,
               interpret: bool | None = None,
               config: dict | None = None,
               w_shifts: jax.Array | None = None) -> jax.Array:
    """SAME stride-1 AdderNet conv (Eq. 3). x: (N,H,W,Cx); w: (HK,HK,Cx,Cy).

    ``bias`` (optional, (Cy,)) is added at accumulator scale before the
    requantization epilogue; ``act="relu"`` fuses the activation at
    accumulator scale after it. ``config`` (a repro.tune schedule dict)
    overrides the block parameters (``block_co``, ``block_n``,
    ``block_h``/``block_w``). ``interpret=None`` auto-detects the backend.

    W4A8: with ``w_shifts`` (per-input-channel group shifts), ``w`` is
    nibble-packed along the Cx axis (``(HK, HK, ceil(Cx/2), Cy)``); the
    kernel unpacks in-register, applies the group shifts, then the usual
    ``w_preshift`` scale alignment. Quantized path only.
    """
    if config:
        block_co = int(config.get("block_co", block_co))
    block_n, block_h, block_w = resolve_tile_config(config, block_n,
                                                    block_h, block_w)
    return _add_conv2d(x, w, bias, w_shifts, block_co=block_co,
                       block_n=block_n,
                       block_h=block_h, block_w=block_w,
                       requant_shift=requant_shift,
                       x_preshift=x_preshift, w_preshift=w_preshift, act=act,
                       out_dtype=out_dtype,
                       interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_co", "block_n", "block_h",
                                             "block_w", "requant_shift",
                                             "x_preshift", "w_preshift",
                                             "act", "out_dtype", "interpret"))
def _add_conv2d(x: jax.Array, w: jax.Array, bias=None, w_shifts=None, *,
                block_co: int = 128,
                block_n: int = 1, block_h: int | None = None,
                block_w: int | None = None,
                requant_shift: int | None = None, x_preshift: int = 0,
                w_preshift: int = 0, act: str | None = None, out_dtype=None,
                interpret: bool = True) -> jax.Array:
    n, h, wd, cx = x.shape
    hk, _, _, cy = w.shape
    w4 = w_shifts is not None
    if w4:
        if requant_shift is None:
            raise ValueError("add_conv2d: W4 weights need the quantized "
                             "path (requant_shift)")
        assert w.shape[2] == (cx + 1) // 2, \
            f"packed Cx extent {w.shape[2]} != ceil({cx}/2)"
    out_dtype = out_dtype or (jnp.int8 if requant_shift is not None else x.dtype)
    if w4:                  # the |x - w| sum runs in the unpacked planes'
        order = w4_plane_order(cx)  # channel order (an exact reordering)
        x, w_shifts = x[..., order], w_shifts[order]
    ph, pw = hk // 2, (hk - 1) // 2
    xp = jnp.pad(x, ((0, 0), (ph, pw), (ph, pw), (0, 0)))
    bco = effective_block(cy, block_co)
    n_co = cy // bco
    bn, bh, bw, n_th, n_tw = batch_spatial_schedule(n, h, wd, block_n,
                                                    block_h, block_w)
    halo = hk - 1
    tiles = halo_tiles(xp, n_th, n_tw, bh, bw, bh + halo, bw + halo)

    def x_index(b, s, cb):
        return (b, s // n_tw, s % n_tw, 0, 0, 0)

    def w_index(b, s, cb):
        return (0, 0, 0, cb)

    def co_index(b, s, cb):
        return (cb,)

    def o_index(b, s, cb):
        return (b, s // n_tw, s % n_tw, cb)

    in_specs = [
        pl.BlockSpec((bn, 1, 1, bh + halo, bw + halo, cx), x_index),
        pl.BlockSpec((hk, hk, (cx + 1) // 2 if w4 else cx, bco), w_index),
    ]
    args = [tiles, w]
    if w4:
        in_specs.append(pl.BlockSpec((cx,), lambda b, s, cb: (0,)))
        args.append(w_shifts)
    if bias is not None:
        in_specs.append(pl.BlockSpec((bco,), co_index))
        args.append(bias)

    def kern(*refs):
        it = iter(refs)
        x_ref, w_ref = next(it), next(it)
        ws_ref = next(it) if w4 else None
        b_ref = next(it) if bias is not None else None
        _kernel(x_ref, w_ref, next(it), hk=hk, bh=bh, bw=bw,
                out_dtype=out_dtype, requant_shift=requant_shift,
                x_preshift=x_preshift, w_preshift=w_preshift,
                act=act, bias_ref=b_ref, ws_ref=ws_ref)
    out = pl.pallas_call(
        kern,
        grid=(n // bn, n_th * n_tw, n_co),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, bh, bw, bco), o_index),
        out_shape=jax.ShapeDtypeStruct((n, n_th * bh, n_tw * bw, cy), out_dtype),
        interpret=interpret,
    )(*args)
    return out[:, :h, :wd, :]
