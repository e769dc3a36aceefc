"""Shift convolution Pallas kernel: fused shifted-gather + pointwise MXU matmul.

The paper modifies im2col's *sampling step* to read each channel at its own
(alpha, beta) offset (§3.3) — the shift itself is free pointer arithmetic.
TPU-native translation: shifts are static layer parameters, so the wrapper
groups channels by identical shift (<= HK^2 distinct values), permutes the
channel axis so groups are contiguous, and the kernel accumulates one
statically-shifted (BN*BH*BW, C_grp) x (C_grp, BCO) MXU matmul per group —
the shifted intermediate map I (Eq. 2) is never materialized.

Grid: (batch_block, spatial_tile, out-channel-block); ``block_n`` images
amortize each pointwise weight-block load and ``block_h``/``block_w`` bound
the VMEM tile (halo = 2*max|shift|).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .common import (acc_dtype, apply_act, apply_requant,
                     batch_spatial_schedule, effective_block, halo_tiles,
                     mxu_dot, resolve_interpret, resolve_tile_config,
                     shift_w4_block, unpack_w4_block)


def _kernel(x_ref, w_ref, o_ref, *, groups, bh, bw, pad, out_dtype,
            requant_shift, act=None, bias_ref=None, ws_ref=None, c=None):
    # x_ref: (BN, 1, 1, BH+2P, BW+2P, C); w_ref: (C, BCO)
    # (W4: (ceil(C/2), BCO) nibble-packed + ws_ref (C,) shifts; unpacked
    # once at kernel top so the odd-sized shift-group slices below never
    # straddle a packed byte)
    adt = acc_dtype(x_ref.dtype)
    bco = w_ref.shape[-1]
    bn = x_ref.shape[0]
    if ws_ref is None:
        wv = w_ref
    else:
        wv = shift_w4_block(unpack_w4_block(w_ref[...], c, 0), ws_ref[...], 0)
    acc = jnp.zeros((bn * bh * bw, bco), adt)
    for start, size, (da, db) in groups:     # static unroll over shift groups
        r0, c0 = pad + da, pad + db
        patch = x_ref[:, 0, 0, r0:r0 + bh, c0:c0 + bw, start:start + size]
        acc = acc + mxu_dot(patch.reshape(bn * bh * bw, size),
                            wv[start:start + size, :], adt)
    if bias_ref is not None:                 # bias at accumulator scale
        acc = acc + bias_ref[...].astype(adt)[None, :]
    acc = apply_act(acc, act)
    acc = apply_requant(acc, requant_shift)
    o_ref[...] = acc.reshape(bn, bh, bw, bco).astype(out_dtype)


def shift_conv2d(x: jax.Array, shifts, w_pw: jax.Array, bias=None, *,
                 block_co: int = 128, block_n: int = 1,
                 block_h: int | None = None, block_w: int | None = None,
                 requant_shift: int | None = None,
                 act: str | None = None,
                 out_dtype=None, interpret: bool | None = None,
                 config: dict | None = None,
                 w_shifts: jax.Array | None = None) -> jax.Array:
    """x: (N,H,W,C); shifts: (C,2) static ints; w_pw: (C,Cy) or (1,1,C,Cy).

    ``bias`` (optional, (Cy,)) is added at accumulator scale before the
    requantization epilogue; ``act="relu"`` fuses the activation at
    accumulator scale after it. ``config`` (a repro.tune schedule dict)
    overrides the block parameters (``block_co``, ``block_n``,
    ``block_h``/``block_w``). ``interpret=None`` auto-detects the backend.

    W4A8: with ``w_shifts`` (per-channel group-scale shifts), ``w_pw`` is
    nibble-packed along the channel axis (``(ceil(C/2), Cy)``). The wrapper
    re-packs along its shift-group channel permutation (pack∘unpack is the
    identity on int4 codes, so this is exact), and the kernel unpacks the
    half-width block in-register before taking the per-group slices.
    Quantized path only.
    """
    if config:
        block_co = int(config.get("block_co", block_co))
    block_n, block_h, block_w = resolve_tile_config(config, block_n,
                                                    block_h, block_w)
    if w_pw.ndim == 4:
        w_pw = w_pw[0, 0]
    n, h, wd, c = x.shape
    cy = w_pw.shape[-1]
    w4 = w_shifts is not None
    if w4:
        if requant_shift is None:
            raise ValueError("shift_conv2d: W4 weights need the quantized "
                             "path (requant_shift)")
        assert w_pw.shape[0] == (c + 1) // 2, \
            f"packed C extent {w_pw.shape[0]} != ceil({c}/2)"
    out_dtype = out_dtype or (jnp.int8 if requant_shift is not None else x.dtype)

    shifts_np = np.asarray(shifts)
    pad = max(1, int(np.abs(shifts_np).max()))
    # group channels by identical shift; permute so groups are contiguous
    order = np.lexsort((shifts_np[:, 1], shifts_np[:, 0]))
    groups = []
    i = 0
    while i < c:
        da, db = shifts_np[order[i]]
        j = i
        while j < c and shifts_np[order[j], 0] == da and shifts_np[order[j], 1] == db:
            j += 1
        groups.append((i, j - i, (int(da), int(db))))
        i = j
    groups = tuple(groups)

    xp = jnp.pad(x[..., order], ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    if w4:
        # permute in code space, then re-pack: the pallas_call still moves
        # only the half-width nibble array
        from repro.core.quantize import pack_w4, unpack_w4
        wp = pack_w4(unpack_w4(w_pw, c, 0)[order, :], 0)
        ws_perm = w_shifts[order]
    else:
        wp = w_pw[order, :]
    bco = effective_block(cy, block_co)
    n_co = cy // bco
    bn, bh, bw, n_th, n_tw = batch_spatial_schedule(n, h, wd, block_n,
                                                    block_h, block_w)
    tiles = halo_tiles(xp, n_th, n_tw, bh, bw, bh + 2 * pad, bw + 2 * pad)

    def x_index(b, s, cb):
        return (b, s // n_tw, s % n_tw, 0, 0, 0)

    def w_index(b, s, cb):
        return (0, cb)

    def co_index(b, s, cb):
        return (cb,)

    def o_index(b, s, cb):
        return (b, s // n_tw, s % n_tw, cb)

    in_specs = [
        pl.BlockSpec((bn, 1, 1, bh + 2 * pad, bw + 2 * pad, c), x_index),
        pl.BlockSpec(((c + 1) // 2 if w4 else c, bco), w_index),
    ]
    args = [tiles, wp]
    if w4:
        in_specs.append(pl.BlockSpec((c,), lambda b, s, cb: (0,)))
        args.append(ws_perm)
    if bias is not None:
        in_specs.append(pl.BlockSpec((bco,), co_index))
        args.append(bias)

    def kern(*refs):
        it = iter(refs)
        x_ref, w_ref = next(it), next(it)
        ws_ref = next(it) if w4 else None
        b_ref = next(it) if bias is not None else None
        _kernel(x_ref, w_ref, next(it), groups=groups, bh=bh, bw=bw, pad=pad,
                out_dtype=out_dtype, requant_shift=requant_shift, act=act,
                bias_ref=b_ref, ws_ref=ws_ref, c=c)
    out = pl.pallas_call(
        kern,
        grid=(n // bn, n_th * n_tw, n_co),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, bh, bw, bco), o_index),
        out_shape=jax.ShapeDtypeStruct((n, n_th * bh, n_tw * bw, cy), out_dtype),
        interpret=resolve_interpret(interpret),
    )(*args)
    return out[:, :h, :wd, :]
