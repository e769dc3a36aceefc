"""Depthwise causal conv1d Pallas kernel — the paper's depthwise primitive
integrated into the LM stack (Mamba / Jamba, and LFM2's short convolutions,
whose prefill runs it on the served path).

Mamba's short (K=4) causal conv1d is exactly a depthwise convolution in 1-D,
so this is the flagship carry-over of the paper's primitive library into the
assigned SSM/hybrid architectures (DESIGN.md §Arch-applicability).

Tiling: grid over (batch, seq-block, channel-block). The K-1 left halo is
obtained without overlapping BlockSpecs by passing the SAME padded array
twice with consecutive index maps (block i-1 supplies the halo tail); the
wrapper left-pads with K-1 zeros so block 0 needs no special casing and
appends one zero block so index i+1 never overruns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import acc_dtype, apply_act, cdiv, effective_block, resolve_interpret


def _kernel(xa_ref, xb_ref, w_ref, o_ref, *, k, bl, out_dtype, act=None):
    adt = acc_dtype(xa_ref.dtype)
    # window rows [0, bl + k - 1): current block + first k-1 rows of next
    window = jnp.concatenate([xa_ref[0], xb_ref[0, :k - 1]], axis=0).astype(adt)
    w = w_ref[...].astype(adt)               # (K, BC)
    acc = jnp.zeros((bl, w.shape[-1]), adt)
    for kk in range(k):                       # static unroll, VPU MACs
        acc = acc + window[kk:kk + bl, :] * w[kk][None, :]
    acc = apply_act(acc, act)
    o_ref[0] = acc.astype(out_dtype)


def causal_conv1d(x: jax.Array, w: jax.Array, *, block_l: int = 512,
                  block_c: int = 512, act: str | None = None,
                  interpret: bool | None = None,
                  config: dict | None = None) -> jax.Array:
    """out[b,l,d] = sum_k w[k,d] * x[b, l-K+1+k, d]. x: (B,L,D); w: (K,D).

    ``act="relu"`` fuses the activation into the epilogue (inference only —
    the ops-layer custom VJP assumes a linear kernel, so the differentiable
    entry point does not expose it). ``config`` (a repro.tune schedule dict)
    overrides the block parameters. ``interpret=None`` auto-detects the
    backend.
    """
    if config:
        block_l = int(config.get("block_l", block_l))
        block_c = int(config.get("block_c", block_c))
    return _causal_conv1d(x, w, block_l=block_l, block_c=block_c, act=act,
                          interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_l", "block_c", "act",
                                             "interpret"))
def _causal_conv1d(x: jax.Array, w: jax.Array, *, block_l: int = 512,
                   block_c: int = 512, act: str | None = None,
                   interpret: bool = True) -> jax.Array:
    b, l, d = x.shape
    k = w.shape[0]
    if w.ndim == 3:                           # accept (K, 1, D)
        w = w[:, 0]
    bl = effective_block(l, block_l)
    bc = effective_block(d, block_c)
    nl = l // bl
    # left halo pad (K-1) + one trailing zero block for the i+1 lookahead ref
    xp = jnp.pad(x, ((0, 0), (k - 1, bl), (0, 0)))
    kern = functools.partial(_kernel, k=k, bl=bl, out_dtype=x.dtype, act=act)
    return pl.pallas_call(
        kern,
        grid=(b, nl, d // bc),
        in_specs=[
            pl.BlockSpec((1, bl, bc), lambda bi, li, ci: (bi, li, ci)),
            pl.BlockSpec((1, bl, bc), lambda bi, li, ci: (bi, li + 1, ci)),
            pl.BlockSpec((k, bc), lambda bi, li, ci: (0, ci)),
        ],
        out_specs=pl.BlockSpec((1, bl, bc), lambda bi, li, ci: (bi, li, ci)),
        out_shape=jax.ShapeDtypeStruct((b, l, d), x.dtype),
        interpret=interpret,
        name="conv1d_causal",
    )(xp, xp, w)
