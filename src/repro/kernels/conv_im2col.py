"""Standard / grouped convolution as lazy-im2col MXU matmuls (Pallas TPU).

TPU adaptation of the paper's CMSIS-NN im2col + __SMLAD path (§3.3):

* Cortex-M materializes 2 im2col columns and re-uses them against 2 filters
  to maximize register-file reuse. The TPU analogue keeps the patch tile in
  VMEM and re-uses it against a BCO-wide *block* of filters on the 128x128
  MXU — "lazy im2col": the HK x HK patch structure is expressed as HK^2
  statically-shifted (BN*BH*BW, Cx) x (Cx, BCO) matmuls accumulated in
  VMEM, so the column matrix is never materialized in HBM at all.
* int8 path: the MXU consumes int8 directly with int32 accumulation, and
  the epilogue applies the paper's Algorithm-1 shift requantization — no
  int16 widening step, unlike __SMLAD.

Grid: (batch_block, spatial_tile, group, out-channel-block). One grid step
owns ``block_n`` images' worth of one halo-padded (block_h, block_w) output
tile, one group, one filter block. Batch blocking amortizes each filter
block load across ``block_n`` images — the Fig-3 data-reuse quantity grows
from Cx*BCO to BN*Cx*BCO MACs per weight byte — while spatial tiling keeps
the VMEM footprint bounded on feature maps larger than the MCU-scale ones
the paper measures (the per-layer blocking argument of "Not All Ops Are
Created Equal!").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (acc_dtype, apply_act, apply_requant,
                     batch_spatial_schedule, effective_block, halo_tiles,
                     mxu_dot, resolve_interpret, resolve_tile_config,
                     w4_plane_order, w4_planes)


def _kernel(x_ref, w_ref, o_ref, *, hk: int, bh: int, bw: int,
            out_dtype, requant_shift: int | None, act: str | None = None,
            bias_ref=None, ws_ref=None):
    # x_ref: (BN, 1, 1, BH+HK-1, BW+HK-1, Cx); w_ref: (HK, HK, Cx, BCO)
    # (W4: (HK, HK, ceil(Cx/2), BCO) nibble-packed + ws_ref (Cx,) shifts;
    # x and the shifts arrive in w4_plane_order)
    cx = x_ref.shape[-1]
    bco = w_ref.shape[-1]
    bn = x_ref.shape[0]
    adt = acc_dtype(x_ref.dtype)
    acc = jnp.zeros((bn * bh * bw, bco), adt)
    if ws_ref is not None:
        ws = ws_ref[...].astype(jnp.int32).reshape(cx, 1)
    for i in range(hk):                      # static unroll: HK^2 MXU calls
        for j in range(hk):
            patch = x_ref[:, 0, 0, i:i + bh, j:j + bw, :]
            a = patch.reshape(bn * bh * bw, cx)
            if ws_ref is None:
                b = w_ref[i, j]
            else:                            # unpack W4 in-register, then the
                b = jnp.left_shift(          # unchanged int8 MXU body
                    w4_planes(w_ref[i, j], cx), ws)
            acc = acc + mxu_dot(a, b, adt)
    if bias_ref is not None:                 # (1, BCO) block
        acc = acc + bias_ref[...].astype(adt)
    # fused activation at accumulator scale, then Algorithm 1: round-to-
    # nearest shift, clip, int8
    acc = apply_act(acc, act)
    acc = apply_requant(acc, requant_shift)
    o_ref[...] = acc.reshape(bn, bh, bw, bco).astype(out_dtype)


def conv2d_im2col(x: jax.Array, w: jax.Array, bias=None, *, groups: int = 1,
                  block_co: int = 128, block_n: int = 1,
                  block_h: int | None = None, block_w: int | None = None,
                  requant_shift: int | None = None,
                  act: str | None = None, out_dtype=None,
                  interpret: bool | None = None,
                  config: dict | None = None,
                  w_shifts: jax.Array | None = None) -> jax.Array:
    """SAME-padded stride-1 conv. x: (N,H,W,Cx); w: (HK,HK,Cx/g,Cy).

    int8 x int8 -> int8 when ``requant_shift`` is given (int32 accumulate);
    float paths accumulate in f32. ``act="relu"`` fuses the activation at
    accumulator scale (after bias, before requantization). ``config`` (a
    repro.tune schedule dict) overrides the block parameters:
    ``block_co`` (filters per step), ``block_n`` (images per step — weight
    reuse), ``block_h``/``block_w`` (halo-padded spatial tile; ``None`` =
    whole map). ``interpret=None`` auto-detects the backend.

    W4A8: passing ``w_shifts`` (the per-input-channel group-scale shifts of
    a ``QTensorW4``) marks ``w`` as nibble-packed along the Cx/g axis
    (extent ``ceil(Cx/g / 2)``); the kernel unpacks in-register so only the
    half-width weight block crosses HBM->VMEM. Quantized path only.
    """
    if config:
        block_co = int(config.get("block_co", block_co))
    block_n, block_h, block_w = resolve_tile_config(config, block_n,
                                                    block_h, block_w)
    return _conv2d_im2col(x, w, bias, w_shifts, groups=groups,
                          block_co=block_co,
                          block_n=block_n, block_h=block_h, block_w=block_w,
                          requant_shift=requant_shift, act=act,
                          out_dtype=out_dtype,
                          interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("groups", "block_co", "block_n",
                                             "block_h", "block_w",
                                             "requant_shift",
                                             "act", "out_dtype", "interpret"))
def _conv2d_im2col(x: jax.Array, w: jax.Array, bias=None, w_shifts=None, *,
                   groups: int = 1,
                   block_co: int = 128, block_n: int = 1,
                   block_h: int | None = None, block_w: int | None = None,
                   requant_shift: int | None = None,
                   act: str | None = None,
                   out_dtype=None, interpret: bool = True) -> jax.Array:
    n, h, wd, cx = x.shape
    hk, _, _, cy = w.shape
    w4 = w_shifts is not None
    cxg = cx // groups if w4 else w.shape[2]
    assert cx == cxg * groups and cy % groups == 0
    if w4:
        if requant_shift is None:
            raise ValueError("conv2d_im2col: W4 weights need the quantized "
                             "path (requant_shift)")
        assert w.shape[2] == (cxg + 1) // 2, \
            f"packed Cx/g extent {w.shape[2]} != ceil({cxg}/2)"
    out_dtype = out_dtype or (jnp.int8 if requant_shift is not None else x.dtype)
    ph, pw = hk // 2, (hk - 1) // 2
    xp = jnp.pad(x, ((0, 0), (ph, pw), (ph, pw), (0, 0)))

    co_per_g = cy // groups
    bco = effective_block(co_per_g, block_co)
    n_co = co_per_g // bco
    bn, bh, bw, n_th, n_tw = batch_spatial_schedule(n, h, wd, block_n,
                                                    block_h, block_w)
    halo = hk - 1
    hp_out, wp_out = n_th * bh, n_tw * bw
    # group-major layout: activations, weights, bias and output carry the
    # group as a LEADING axis, so every block's last two dims are the
    # group's whole channel extent (or a lane-aligned channel block) — the
    # TPU tiling rule holds for any group count. groups=1 is a free reshape.
    cxw = w.shape[2]
    xg = jnp.moveaxis(xp.reshape(xp.shape[:3] + (groups, cxg)), 3, 0)
    if w4:                  # the contraction runs in the unpacked planes'
        order = w4_plane_order(cxg)  # channel order (an exact reordering)
        xg, w_shifts = xg[..., order], w_shifts[order]
    tiles = halo_tiles(xg.reshape((groups * n,) + xg.shape[2:]), n_th, n_tw,
                       bh, bw, bh + halo, bw + halo)
    tiles = tiles.reshape((groups, n) + tiles.shape[1:])
    wg = jnp.moveaxis(w.reshape(hk, hk, cxw, groups, co_per_g), 3, 0)

    # index maps close over the RESOLVED schedule (n_co/n_tw computed from
    # the effective blocks above) — no default-arg captures, so a config
    # that rounds through effective_block can never leave a stale divisor
    # in the lambdas
    def x_index(b, s, g, c):
        return (g, b, s // n_tw, s % n_tw, 0, 0, 0)

    def w_index(b, s, g, c):
        return (g, 0, 0, 0, c)

    def co_index(b, s, g, c):
        return (g, 0, c)

    def o_index(b, s, g, c):
        return (g, b, s // n_tw, s % n_tw, c)

    in_specs = [
        pl.BlockSpec((None, bn, 1, 1, bh + halo, bw + halo, cxg), x_index),
        pl.BlockSpec((None, hk, hk, cxw, bco), w_index),
    ]
    args = [tiles, wg]
    if w4:                  # shifts ride whole (the packed axis is unblocked)
        in_specs.append(pl.BlockSpec((cxg,), lambda b, s, g, c: (0,)))
        args.append(w_shifts)
    if bias is not None:
        in_specs.append(pl.BlockSpec((None, 1, bco), co_index))
        args.append(bias.reshape(groups, 1, co_per_g))

    def kern(*refs):
        it = iter(refs)
        x_ref, w_ref = next(it), next(it)
        ws_ref = next(it) if w4 else None
        b_ref = next(it) if bias is not None else None
        _kernel(x_ref, w_ref, next(it), hk=hk, bh=bh, bw=bw,
                out_dtype=out_dtype, requant_shift=requant_shift,
                act=act, bias_ref=b_ref, ws_ref=ws_ref)

    out = pl.pallas_call(
        kern,
        grid=(n // bn, n_th * n_tw, groups, n_co),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, bn, bh, bw, bco), o_index),
        out_shape=jax.ShapeDtypeStruct((groups, n, hp_out, wp_out, co_per_g),
                                       out_dtype),
        interpret=interpret,
    )(*args)
    out = jnp.moveaxis(out, 0, 3).reshape(n, hp_out, wp_out, cy)
    return out[:, :h, :wd, :]
