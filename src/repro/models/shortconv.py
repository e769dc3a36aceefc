"""LFM2's gated short convolution: ``B, C, x = split3(h W_in)``,
``y = (C * conv(B * x)) W_out``, where ``conv`` is a depthwise causal
convolution over time of ``K`` taps per channel, without bias.

Prefill runs the convolution through the paper's depthwise primitive
(``kernels/conv1d_causal.py``, via ``kernels.ops.causal_conv1d``). Decode
keeps, per sequence, the last ``K - 1`` positions of ``B * x`` (the conv
state) and updates it one token at a time, summing the taps in the
kernel's order and precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def init_shortconv(key, d: int, k: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"in_proj": jax.random.normal(k1, (d, 3 * d), dtype) * d ** -0.5,
            "conv_w": jax.random.normal(k2, (k, d), dtype) * k ** -0.5,
            "out_proj": jax.random.normal(k3, (d, d), dtype) * d ** -0.5}


def _gates(p, x, cdt):
    b, c, xx = jnp.split(x.astype(cdt) @ p["in_proj"].astype(cdt), 3, axis=-1)
    return b * xx, c


def shortconv_forward(p, x, cdt, lengths=None):
    """x: (B, S, d) -> (y (B, S, d), state (B, K - 1, d)).

    ``state`` holds ``B * x`` at the ``K - 1`` positions before each row's
    length (``lengths``, (B,) int32; default S), zeros before position 0:
    what decode continues from. Positions past a row's length (bucket
    padding) do not reach its real positions: the convolution is causal.
    """
    from repro.kernels.ops import causal_conv1d
    from .mamba import _resolve_conv_method
    bx, c = _gates(p, x, cdt)
    w = p["conv_w"].astype(cdt)
    conv = causal_conv1d(bx, w, method=_resolve_conv_method("auto"))
    y = (c * conv) @ p["out_proj"].astype(cdt)
    k = w.shape[0]
    b, s, _ = bx.shape
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    padded = jnp.pad(bx, ((0, 0), (k - 1, 0), (0, 0)))
    state = jax.vmap(lambda r, n: lax.dynamic_slice_in_dim(r, n, k - 1))(
        padded, jnp.asarray(lengths, jnp.int32))
    return y, state


def shortconv_step(p, x, state, cdt):
    """One token. x: (B, 1, d); state: (B, K - 1, d) -> (y, new state)."""
    bx, c = _gates(p, x, cdt)
    win = jnp.concatenate([state.astype(bx.dtype), bx], axis=1)  # (B, K, d)
    w = p["conv_w"].astype(cdt).astype(jnp.float32)
    acc = jnp.zeros(bx[:, 0].shape, jnp.float32)
    for kk in range(w.shape[0]):            # the kernel's order, in float32
        acc = acc + win[:, kk].astype(jnp.float32) * w[kk][None, :]
    y = (c * acc[:, None].astype(cdt)) @ p["out_proj"].astype(cdt)
    return y, win[:, 1:]
