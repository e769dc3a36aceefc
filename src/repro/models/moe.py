"""Mixture-of-Experts with sort-free capacity dispatch + expert parallelism.

Dispatch is scatter-based (GShard-style capacity + dropping) but never
materializes a (tokens, E, C) one-hot: position-in-expert comes from a
cumsum over a (tokens, E) one-hot and tokens scatter into a dense
(E, C, d) buffer. Two execution paths:

  * local   — no collectives; used on 1 device and as the test oracle.
  * sharded — shard_map over ("model",): tokens are split across the model
    axis (token parallelism), dispatched locally, then exchanged with
    all_to_all so each model shard computes only its E/nm local experts
    (expert parallelism), and a2a'd back. DP/pod axes stay batch-parallel.
    This is the DeepSpeed-MoE / GShard layout mapped to jax collectives.

A pattern stack (``ModelConfig.layer_types``, LFM2) serves its expert
layers through ``moe_ffn_dropless`` instead: every routed token is
computed by the ``moe_experts`` kernel, so a token's result never depends
on what else is in the batch. Its expert matrices are stored (E, ff, d)
(``init_moe_dropless``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.parallel.sharding import active_rules, current_mesh

from .blocks import mlp


def init_moe(key, d: int, moe: MoEConfig, act: str, dtype):
    ks = jax.random.split(key, 4)
    e, ff = moe.num_experts, moe.d_ff
    s_in, s_out = d ** -0.5, ff ** -0.5
    p = {"router": jax.random.normal(ks[0], (d, e), dtype) * s_in,
         "w_up": jax.random.normal(ks[1], (e, d, ff), dtype) * s_in,
         "w_down": jax.random.normal(ks[2], (e, ff, d), dtype) * s_out}
    if act == "silu":
        p["w_gate"] = jax.random.normal(ks[3], (e, d, ff), dtype) * s_in
    return p


def init_moe_dropless(key, d: int, moe: MoEConfig, dtype):
    """Router (d, E), selection bias (E,) when ``moe.expert_bias``, and the
    SwiGLU experts stored (E, ff, d), the expert width leading (the layout
    ``kernels/moe_experts.py`` reads in contiguous chunks)."""
    ks = jax.random.split(key, 4)
    e, ff = moe.num_experts, moe.d_ff
    p = {"router": jax.random.normal(ks[0], (d, e), dtype) * d ** -0.5,
         "w_gate": jax.random.normal(ks[1], (e, ff, d), dtype) * d ** -0.5,
         "w_up": jax.random.normal(ks[2], (e, ff, d), dtype) * d ** -0.5,
         "w_down": jax.random.normal(ks[3], (e, ff, d), dtype) * ff ** -0.5}
    if moe.expert_bias:
        p["bias"] = jnp.zeros((e,), dtype)
    return p


def moe_specs(act, prefix_layers=True):
    L = ("layers",) if prefix_layers else ()
    p = {"router": L + ("embed", None),
         "w_up": L + ("experts", "embed", "ffn_expert"),
         "w_down": L + ("experts", "ffn_expert", "embed")}
    if act == "silu":
        p["w_gate"] = L + ("experts", "embed", "ffn_expert")
    return p


def _capacity(tokens: int, moe: MoEConfig) -> int:
    c = int(moe.top_k * tokens * moe.capacity_factor / moe.num_experts)
    return max(c, 1)


def _dispatch_combine(x2d, probs, ids, expert_fn, num_experts: int, cap: int):
    """Scatter tokens to (E, C, d), run expert_fn, gather-combine back."""
    t, d = x2d.shape
    k = ids.shape[1]
    flat_ids = ids.reshape(-1)                            # (T*k,)
    tok_idx = jnp.repeat(jnp.arange(t), k)
    oh = jax.nn.one_hot(flat_ids, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - 1                      # running count
    pos = jnp.take_along_axis(pos, flat_ids[:, None], axis=1)[:, 0]
    keep = pos < cap
    dest = jnp.where(keep, flat_ids * cap + pos, num_experts * cap)  # drop slot
    disp = jnp.zeros((num_experts * cap + 1, d), x2d.dtype)
    disp = disp.at[dest].add(x2d[tok_idx] * keep[:, None].astype(x2d.dtype))
    h = expert_fn(disp[:-1].reshape(num_experts, cap, d))
    h = h.reshape(num_experts * cap, d)
    h = jnp.concatenate([h, jnp.zeros((1, d), h.dtype)], axis=0)
    gathered = h[dest] * (probs.reshape(-1)[:, None].astype(h.dtype)
                          * keep[:, None].astype(h.dtype))
    out = jnp.zeros((t, d), x2d.dtype)
    return out.at[tok_idx].add(gathered.astype(x2d.dtype))


def _expert_ffn(blocks, p, act, cdt):
    """blocks: (E_local, C, d); expert weights (E_local, d, ff)/(E_local, ff, d)."""
    blocks = blocks.astype(cdt)     # keep the MXU path in compute dtype —
    # a stray f32 operand would promote (and LICM-hoist an f32 copy of) the
    # whole stacked expert-weight tensor
    up = jnp.einsum("ecd,edf->ecf", blocks, p["w_up"].astype(cdt))
    if act == "silu":
        gate = jnp.einsum("ecd,edf->ecf", blocks, p["w_gate"].astype(cdt))
        z = jax.nn.silu(gate) * up
    else:
        z = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", z, p["w_down"].astype(cdt))


def moe_ffn_local(x, p, moe: MoEConfig, act: str, cdt):
    """(B, S, d) -> (B, S, d), no collectives."""
    b, s, d = x.shape
    x2 = x.reshape(-1, d)
    probs, ids = route(x2, p, moe)
    cap = _capacity(x2.shape[0], moe)
    fn = functools.partial(_expert_ffn, p=p, act=act, cdt=cdt)
    y = _dispatch_combine(x2, probs, ids, lambda blk: fn(blk), moe.num_experts, cap)
    return y.reshape(b, s, d)


# decode paths prefer the local (pjit-constraint) path: one token per slot
# is too small for the token-split + a2a pipeline to pay off.
_PREFER_LOCAL: list = [False]


class prefer_local:
    def __enter__(self):
        _PREFER_LOCAL.append(True)

    def __exit__(self, *exc):
        _PREFER_LOCAL.pop()


def moe_ffn_sharded(x, p, moe: MoEConfig, act: str, cdt, model_axis="model"):
    """shard_map EP path. x: (B, S, d) with batch sharded over the DP axes and
    d replicated across model_axis; experts sharded over model_axis."""
    mesh = current_mesh()
    if mesh is None or mesh.shape.get(model_axis, 1) == 1 \
            or moe.num_experts % mesh.shape.get(model_axis, 1) \
            or _PREFER_LOCAL[-1]:
        return moe_ffn_local(x, p, moe, act, cdt)
    kept, size = [], 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and x.shape[0] % (size * mesh.shape[a]) == 0:
            kept.append(a)
            size *= mesh.shape[a]
    batch_axes = tuple(kept)
    nm = mesh.shape[model_axis]
    e_local = moe.num_experts // nm
    d = x.shape[-1]

    def body(xb, router, *expert_w):
        pw = dict(zip(sorted(k for k in p if k != "router"), expert_w))
        bl, sl, _ = xb.shape
        t2 = xb.reshape(-1, d)
        t_pad = -(-t2.shape[0] // nm) * nm
        t2p = jnp.pad(t2, ((0, t_pad - t2.shape[0]), (0, 0)))
        tloc = t_pad // nm
        j = lax.axis_index(model_axis)
        xj = lax.dynamic_slice_in_dim(t2p, j * tloc, tloc)      # token split (TP->token-parallel)
        probs, ids = route(xj, {"router": router}, moe)
        cap = _capacity(tloc, moe)

        def experts_a2a(blocks):                 # (E, C, d) global experts
            de = lax.all_to_all(blocks, model_axis, split_axis=0,
                                concat_axis=1, tiled=True)      # (E/nm, nm*C, d)
            h = _expert_ffn(de, pw, act, cdt)
            return lax.all_to_all(h, model_axis, split_axis=1,
                                  concat_axis=0, tiled=True)    # (E, C, d)

        yj = _dispatch_combine(xj, probs, ids, experts_a2a,
                               moe.num_experts, cap)
        y = lax.all_gather(yj, model_axis, axis=0, tiled=True)  # (t_pad, d)
        return y[:t2.shape[0]].reshape(bl, sl, d)

    from jax.experimental.shard_map import shard_map
    batch_spec = P(batch_axes, None, None) if batch_axes else P(None, None, None)
    expert_keys = sorted(k for k in p if k != "router")
    expert_specs = tuple(P(model_axis, None, None) for _ in expert_keys)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(batch_spec, P(None, None)) + expert_specs,
        out_specs=batch_spec, check_rep=False)
    # cast expert weights to the compute dtype BEFORE they cross the
    # shard_map boundary: a promotion inside would be LICM-hoisted into a
    # full f32 copy of the stacked expert tensors
    return fn(x, p["router"], *[p[k].astype(cdt) for k in expert_keys])


def route(x2d, p, moe: MoEConfig):
    """Top-k experts (T, k) and their weights, in float32.

    ``router="softmax"``: softmax over the top-k logits. ``"sigmoid"``:
    sigmoid scores, the top-k picked on score + selection bias (when
    ``expert_bias``), weighted by their own scores normalised over the
    top-k (+ 1e-6)."""
    logits = jnp.dot(x2d.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if moe.router != "sigmoid":
        vals, ids = lax.top_k(logits, moe.top_k)
        return jax.nn.softmax(vals, axis=-1), ids
    scores = jax.nn.sigmoid(logits)
    pick = scores + p["bias"].astype(jnp.float32) if moe.expert_bias \
        else scores
    _, ids = lax.top_k(pick, moe.top_k)
    w = jnp.take_along_axis(scores, ids, axis=1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6), ids


def moe_ffn_dropless(x, p, moe: MoEConfig, cdt):
    """(B, S, d) -> ((B, S, d), (E,) int32 assignments per expert): every
    routed token through the ``moe_experts`` kernel, none dropped."""
    from repro.kernels.moe_experts import moe_experts
    b, s, d = x.shape
    x2 = x.reshape(-1, d)
    w, ids = route(x2, p, moe)
    y, counts = moe_experts(x2.astype(cdt), ids, w, p["w_gate"].astype(cdt),
                            p["w_up"].astype(cdt), p["w_down"].astype(cdt))
    return y.reshape(b, s, d).astype(cdt), counts


def moe_ffn(x, p, moe: MoEConfig, act: str, cdt):
    return moe_ffn_sharded(x, p, moe, act, cdt)
