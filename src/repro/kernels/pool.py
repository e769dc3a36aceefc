"""int8 max-pooling Pallas kernel (VPU path).

NNoM's integer pipeline pools BETWEEN the int8 requantization of one conv
and the int8 consumption of the next — max commutes with the (positive,
power-of-two) dequantization scale, so pooling int8 codes is bit-exact with
pooling the dequantized floats. This kernel is what lets the graph executor
keep activations int8 across pool boundaries (zero float round-trips).

Grid: (batch_block, spatial_tile, channel-block); one grid step reduces a
``block_n``-image, halo-padded (``block_h``, ``block_w``) OUTPUT tile as
W^2 element-wise maxima on the 8x128 VPU — the same shifted accumulation
pattern as conv_dw, with max replacing multiply-add. The TPU has no strided
vector loads for int8, so the wrapper splits the map into its stride
phases (``x[:, pi::s, pj::s]``, one XLA strided slice each); window tap
``(i, j)`` is then a unit-stride slice of phase ``(i % s, j % s)`` at
offset ``(i // s, j // s)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (acc_dtype, batch_spatial_schedule, effective_block,
                     halo_tiles, resolve_interpret, resolve_tile_config)


def _kernel(x_ref, o_ref, *, win, stride, bh, bw):
    # x_ref: (BN, P, 1, 1, BH + (win-1)//s, BW + (win-1)//s, BC), P phases
    nph = min(win, stride)
    # the v5e VPU has no 8-bit max: compare int8 codes as int32 (exact)
    acc = acc_dtype(x_ref.dtype)
    out = None
    for i in range(win):                     # static unroll over window taps
        for j in range(win):
            r0, c0 = i // stride, j // stride
            v = x_ref[:, (i % stride) * nph + j % stride, 0, 0,
                      r0:r0 + bh, c0:c0 + bw, :].astype(acc)
            out = v if out is None else jnp.maximum(out, v)
    o_ref[...] = out.astype(o_ref.dtype)


def maxpool2d(x: jax.Array, *, window: int = 2, stride: int | None = None,
              block_c: int = 128, block_n: int = 1,
              block_h: int | None = None, block_w: int | None = None,
              interpret: bool | None = None,
              config: dict | None = None) -> jax.Array:
    """VALID max-pool. x: (N,H,W,C) — int8 (the fused-graph path) or float.

    ``config`` (a repro.tune schedule dict) overrides the block parameters
    (``block_c``, ``block_n``, ``block_h``/``block_w`` — OUTPUT-tile
    extents). ``interpret=None`` auto-detects the backend.
    """
    if config:
        block_c = int(config.get("block_c", block_c))
    block_n, block_h, block_w = resolve_tile_config(config, block_n,
                                                    block_h, block_w)
    return _maxpool2d(x, window=window, stride=stride or window,
                      block_c=block_c, block_n=block_n, block_h=block_h,
                      block_w=block_w, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("window", "stride", "block_c",
                                             "block_n", "block_h", "block_w",
                                             "interpret"))
def _maxpool2d(x: jax.Array, *, window: int, stride: int, block_c: int,
               block_n: int = 1, block_h: int | None = None,
               block_w: int | None = None,
               interpret: bool = True) -> jax.Array:
    n, h, w, c = x.shape
    hout = (h - window) // stride + 1
    wout = (w - window) // stride + 1
    bc = effective_block(c, block_c)
    bn, bh, bw, n_th, n_tw = batch_spatial_schedule(n, hout, wout, block_n,
                                                    block_h, block_w)
    # stride phases: output row r, tap i reads input row s*(r + i//s) + i%s,
    # i.e. row r + i//s of phase i%s; each phase keeps the rows the valid
    # outputs need (pad rows only feed output rows the final crop discards)
    nph, halo = min(window, stride), (window - 1) // stride
    need_h, need_w = hout + halo, wout + halo
    phases = []
    for pi in range(nph):
        for pj in range(nph):
            ph = x[:, pi::stride, pj::stride, :][:, :need_h, :need_w, :]
            phases.append(jnp.pad(ph, ((0, 0), (0, need_h - ph.shape[1]),
                                       (0, need_w - ph.shape[2]), (0, 0))))
    xph = jnp.stack(phases, axis=1).reshape(n * nph * nph, need_h, need_w, c)
    # output tile (bh, bw) consumes phase rows [th*bh, th*bh + bh + halo)
    tiles = halo_tiles(xph, n_th, n_tw, bh, bw, bh + halo, bw + halo)
    tiles = tiles.reshape((n, nph * nph) + tiles.shape[1:])

    def x_index(b, s, cb):
        return (b, 0, s // n_tw, s % n_tw, 0, 0, cb)

    def o_index(b, s, cb):
        return (b, s // n_tw, s % n_tw, cb)

    kern = functools.partial(_kernel, win=window, stride=stride, bh=bh, bw=bw)
    out = pl.pallas_call(
        kern,
        grid=(n // bn, n_th * n_tw, c // bc),
        in_specs=[pl.BlockSpec((bn, nph * nph, 1, 1, bh + halo, bw + halo,
                                bc), x_index)],
        out_specs=pl.BlockSpec((bn, bh, bw, bc), o_index),
        out_shape=jax.ShapeDtypeStruct((n, n_th * bh, n_tw * bw, c), x.dtype),
        interpret=interpret,
    )(tiles)
    return out[:, :hout, :wout, :]
