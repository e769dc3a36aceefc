"""Depthwise convolution Pallas kernel (VPU path).

Depthwise conv has no channel contraction, so the MXU is idle — like the
paper's observation (via Jeon & Kim) that depthwise is *slower per MAC*
than pointwise on real hardware despite fewer MACs. On TPU it runs on the
8x128 VPU as HK^2 shifted element-wise multiply-accumulates; channels map
to the 128-lane dimension. Used standalone (dws primitive, stage 1) and as
the reference pattern for the Mamba causal conv1d kernel.

Grid: (batch_block, spatial_tile, channel-block). ``block_n`` images share
each filter-slice load per grid step and ``block_h``/``block_w`` bound the
halo-padded VMEM tile on large feature maps (same schedule family as
conv_im2col).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (acc_dtype, apply_act, apply_requant,
                     batch_spatial_schedule, effective_block, halo_tiles,
                     resolve_interpret, resolve_tile_config, w4_nibble)


def _kernel(x_ref, w_ref, o_ref, *, hk, bh, bw, out_dtype, requant_shift,
            act=None, ws_ref=None):
    # x_ref: (BN, 1, 1, BH+HK-1, BW+HK-1, BC); w_ref: (HK, HK, BC)
    # (W4: (ceil(HK/2), HK, BC) nibble-packed along the tap-row axis —
    # channels stay the blocked 128-lane axis — + ws_ref (HK, BC) shifts)
    adt = acc_dtype(x_ref.dtype)
    bc = w_ref.shape[-1]
    bn = x_ref.shape[0]
    acc = jnp.zeros((bn, bh, bw, bc), adt)
    for i in range(hk):
        if ws_ref is None:
            wrow = w_ref[i]                  # (HK, BC)
        else:                                # tap row i: nibble i % 2 of
            wrow = jnp.left_shift(           # packed row i // 2, 2-D only
                w4_nibble(w_ref[i // 2], i % 2), ws_ref[i:i + 1, :])
        for j in range(hk):
            acc = acc + (x_ref[:, 0, 0, i:i + bh, j:j + bw, :].astype(adt)
                         * wrow[j].astype(adt)[None, None, None, :])
    acc = apply_act(acc, act)
    acc = apply_requant(acc, requant_shift)
    o_ref[...] = acc.astype(out_dtype)


def depthwise2d(x: jax.Array, w_dw: jax.Array, *, block_c: int = 128,
                block_n: int = 1, block_h: int | None = None,
                block_w: int | None = None,
                requant_shift: int | None = None, act: str | None = None,
                out_dtype=None,
                interpret: bool | None = None,
                config: dict | None = None,
                w_shifts: jax.Array | None = None) -> jax.Array:
    """SAME stride-1 depthwise conv. x: (N,H,W,C); w_dw: (HK,HK,C).

    ``act="relu"`` fuses the activation at accumulator scale before the
    requantization epilogue. ``config`` (a repro.tune schedule dict)
    overrides the block parameters (``block_c``, ``block_n``,
    ``block_h``/``block_w``). ``interpret=None`` auto-detects the backend.

    W4A8: with ``w_shifts`` (per-tap-row group shifts), ``w_dw`` is
    nibble-packed along the tap-row axis — ``(ceil(HK/2), HK, C)`` — so the
    channel axis keeps arbitrary ``block_c`` blocking while the weight block
    crossing HBM->VMEM is halved. Quantized path only.
    """
    if config:
        block_c = int(config.get("block_c", block_c))
    block_n, block_h, block_w = resolve_tile_config(config, block_n,
                                                    block_h, block_w)
    return _depthwise2d(x, w_dw, w_shifts, block_c=block_c, block_n=block_n,
                        block_h=block_h, block_w=block_w,
                        requant_shift=requant_shift,
                        act=act, out_dtype=out_dtype,
                        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_c", "block_n", "block_h",
                                             "block_w", "requant_shift",
                                             "act", "out_dtype", "interpret"))
def _depthwise2d(x: jax.Array, w_dw: jax.Array, w_shifts=None, *,
                 block_c: int = 128,
                 block_n: int = 1, block_h: int | None = None,
                 block_w: int | None = None,
                 requant_shift: int | None = None, act: str | None = None,
                 out_dtype=None,
                 interpret: bool = True) -> jax.Array:
    n, h, wd, c = x.shape
    w4 = w_shifts is not None
    if w_dw.ndim == 4:                       # accept (HK,HK,C,1) layout
        w_dw = w_dw[..., 0]
    hk = w_dw.shape[1] if w4 else w_dw.shape[0]
    if w4:
        if requant_shift is None:
            raise ValueError("depthwise2d: W4 weights need the quantized "
                             "path (requant_shift)")
        assert w_dw.shape[0] == (hk + 1) // 2, \
            f"packed HK extent {w_dw.shape[0]} != ceil({hk}/2)"
    out_dtype = out_dtype or (jnp.int8 if requant_shift is not None else x.dtype)
    ph, pw = hk // 2, (hk - 1) // 2
    xp = jnp.pad(x, ((0, 0), (ph, pw), (ph, pw), (0, 0)))
    bc = effective_block(c, block_c)
    bn, bh, bw, n_th, n_tw = batch_spatial_schedule(n, h, wd, block_n,
                                                    block_h, block_w)
    halo = hk - 1
    tiles = halo_tiles(xp, n_th, n_tw, bh, bw, bh + halo, bw + halo)

    def x_index(b, s, cb):
        return (b, s // n_tw, s % n_tw, 0, 0, cb)

    def w_index(b, s, cb):
        return (0, 0, cb)

    def o_index(b, s, cb):
        return (b, s // n_tw, s % n_tw, cb)

    in_specs = [
        pl.BlockSpec((bn, 1, 1, bh + halo, bw + halo, bc), x_index),
        pl.BlockSpec(((hk + 1) // 2 if w4 else hk, hk, bc), w_index),
    ]
    args = [tiles, w_dw]
    if w4:
        # per-tap-row shifts spread over the channel lanes: the VPU can
        # broadcast a (1, BC) row across sublanes, not a (1, 1) scalar
        in_specs.append(pl.BlockSpec((hk, bc), lambda b, s, cb: (0, cb)))
        args.append(jnp.broadcast_to(w_shifts.astype(jnp.int32)[:, None],
                                     (hk, c)))

    def kern(*refs):
        it = iter(refs)
        x_ref, w_ref = next(it), next(it)
        ws_ref = next(it) if w4 else None
        _kernel(x_ref, w_ref, next(it), hk=hk, bh=bh, bw=bw,
                out_dtype=out_dtype, requant_shift=requant_shift, act=act,
                ws_ref=ws_ref)

    out = pl.pallas_call(
        kern,
        grid=(n // bn, n_th * n_tw, c // bc),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, bh, bw, bc), o_index),
        out_shape=jax.ShapeDtypeStruct((n, n_th * bh, n_tw * bw, c), out_dtype),
        interpret=interpret,
    )(*args)
    return out[:, :h, :wd, :]
