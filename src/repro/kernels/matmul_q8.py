"""Tiled MXU matmul kernel with the paper's int8 power-of-two requantization.

Backs the pointwise stage of dws/shift at LM scale and the optional
integer-only serve path (DESIGN.md: Eq. 4 / Algorithm 1 applied to LM
matmuls). Classic 3-D grid (M/BM, N/BN, K/BK): the K axis is the innermost
("arbitrary") dimension and the output block is revisited across K steps,
accumulating in VMEM; on the last K step the epilogue applies bias + the
Algorithm-1 arithmetic shift and clips to int8. bf16/f32 paths share the
same schedule with an f32 accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (acc_dtype, apply_act, apply_requant, cdiv,
                     contraction_block, mxu_dot, resolve_interpret,
                     shift_w4_block, unpack_w4_block)


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, nk, out_dtype, requant_shift,
            act=None, ws_ref=None):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if ws_ref is None:
        bv = b_ref[...]
    else:
        # W4: b block is (ceil(BK/2), BN) nibble-packed along K; an odd
        # whole-K block's tail nibble is a zero code, sliced off here
        bv = shift_w4_block(
            unpack_w4_block(b_ref[...], a_ref.shape[1], 0), ws_ref[...], 0)
    acc_ref[...] += mxu_dot(a_ref[...], bv, acc_ref.dtype)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        acc = apply_act(acc_ref[...], act)
        o_ref[...] = apply_requant(acc, requant_shift).astype(out_dtype)


def matmul(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
           bk: int = 512, requant_shift: int | None = None,
           act: str | None = None, out_dtype=None,
           interpret: bool | None = None,
           config: dict | None = None,
           w_shifts: jax.Array | None = None) -> jax.Array:
    """a: (M, K) or (N_batch, M, K) @ b: (K, N). int8 inputs +
    requant_shift -> int8 output.

    W4A8: with ``w_shifts`` (per-K group-scale shifts), ``b`` is
    nibble-packed along K (``(ceil(K/2), N)``); the K block size is forced
    even so packed blocks never straddle a byte, and the kernel unpacks
    in-register — only the half-width weight block crosses HBM->VMEM.
    Quantized path only.

    A 3-D ``a`` is the batched serving path: the leading batch dim is
    folded into M, so one kernel launch covers the whole microbatch and the
    ``bm`` grid tiles the combined batch-row axis — each ``b`` block load
    is amortized across every image in the batch (the same weight-reuse
    schedule as the conv kernels' ``block_n``), and batched-vs-looped is
    bit-exact by construction (identical per-row contractions).

    ``act="relu"`` fuses the activation at accumulator scale on the last
    K step, before requantization. ``config`` (a repro.tune schedule dict)
    overrides the block parameters. ``interpret=None`` auto-detects the
    backend.
    """
    if config:
        bm = int(config.get("bm", bm))
        bn = int(config.get("bn", bn))
        bk = int(config.get("bk", bk))
    if a.ndim == 3:
        nb, m, k = a.shape
        out = _matmul(a.reshape(nb * m, k), b, w_shifts, bm=bm, bn=bn, bk=bk,
                      requant_shift=requant_shift, act=act,
                      out_dtype=out_dtype,
                      interpret=resolve_interpret(interpret))
        return out.reshape(nb, m, b.shape[-1])
    return _matmul(a, b, w_shifts, bm=bm, bn=bn, bk=bk,
                   requant_shift=requant_shift,
                   act=act, out_dtype=out_dtype,
                   interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "requant_shift",
                                             "act", "out_dtype", "interpret"))
def _matmul(a: jax.Array, b: jax.Array, w_shifts=None, *, bm: int = 256,
            bn: int = 256,
            bk: int = 512, requant_shift: int | None = None,
            act: str | None = None, out_dtype=None,
            interpret: bool = True) -> jax.Array:
    m, k = a.shape
    w4 = w_shifts is not None
    k2, n = b.shape
    if w4:
        if requant_shift is None:
            raise ValueError("matmul: W4 weights need the quantized path "
                             "(requant_shift)")
        assert k2 == (k + 1) // 2, f"packed K extent {k2} != ceil({k}/2)"
    else:
        assert k == k2
    out_dtype = out_dtype or (jnp.int8 if requant_shift is not None else a.dtype)
    adt = acc_dtype(a.dtype)
    # ragged M/N blocks only write rows/cols the output crops; K blocks
    # tile K exactly (a partial K block would pollute the accumulator).
    # Lane-aligned K blocks are even, so packed W4 blocks never straddle a
    # byte; an odd K runs as one whole block with a zero tail nibble.
    bm_, bn_ = min(bm, m), min(bn, n)
    bk_ = contraction_block(k, bk)
    grid = (cdiv(m, bm_), cdiv(n, bn_), k // bk_)
    in_specs = [
        pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
        pl.BlockSpec(((bk_ + 1) // 2 if w4 else bk_, bn_),
                     lambda i, j, kk: (kk, j)),
    ]
    args = [a, b]
    if w4:
        # (1, K): a rank-1 int8 block must span the array or 512 lanes
        in_specs.append(pl.BlockSpec((1, bk_), lambda i, j, kk: (0, kk)))
        args.append(w_shifts.reshape(1, k))

    def kern(*refs):
        it = iter(refs)
        a_ref, b_ref = next(it), next(it)
        ws_ref = next(it) if w4 else None
        o_ref = next(it)
        _kernel(a_ref, b_ref, o_ref, next(it), nk=grid[2],
                out_dtype=out_dtype, requant_shift=requant_shift, act=act,
                ws_ref=ws_ref)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), adt)],
        interpret=interpret,
    )(*args)
