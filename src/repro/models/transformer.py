"""Decoder-only LM covering the dense / moe / vlm / ssm / hybrid families.

Layers are scanned with stacked parameters (HLO size is O(1) in depth; FSDP
all-gathers happen per scan step so XLA's latency-hiding scheduler can
overlap them with compute). The hybrid (Jamba) family scans over
super-blocks of ``attn_period`` sublayers (7 mamba + 1 attention, MoE on
every other FFN) so the heterogeneous interleave stays scan-friendly.

A config with ``layer_types`` (LFM2) is a *pattern stack*: an irregular
interleave of gated short convolutions and attention layers, a few leading
dense FFNs and dropless MoE after. Its layers are a list of per-layer
parameter dicts, run unrolled (see "pattern stacks" below).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import paged_attention
from repro.parallel.sharding import constrain

from . import attention as A
from .blocks import cross_entropy, init_mlp, mlp, mlp_specs, rmsnorm, rope
from .mamba import (init_mamba, mamba_decode_step, mamba_forward,
                    mamba_init_state, mamba_specs)
from .moe import (init_moe, init_moe_dropless, moe_ffn, moe_ffn_dropless,
                  moe_specs)
from .shortconv import init_shortconv, shortconv_forward, shortconv_step


def _cdt(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _pdt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ===================================================================== init

def init_attn(key, cfg: ModelConfig, dtype):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {"wq": jax.random.normal(ks[0], (d, hq * dh), dtype) * s,
         "wk": jax.random.normal(ks[1], (d, hkv * dh), dtype) * s,
         "wv": jax.random.normal(ks[2], (d, hkv * dh), dtype) * s,
         "wo": jax.random.normal(ks[3], (hq * dh, d), dtype) * (hq * dh) ** -0.5}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def attn_specs(cfg: ModelConfig, prefix_layers=True):
    L = ("layers",) if prefix_layers else ()
    p = {"wq": L + ("embed", "heads"), "wk": L + ("embed", "kv_heads"),
         "wv": L + ("embed", "kv_heads"), "wo": L + ("heads", "embed")}
    if cfg.qkv_bias:
        p.update({"bq": L + ("heads",), "bk": L + ("kv_heads",),
                  "bv": L + ("kv_heads",)})
    return p


def _stack_init(key, n: int, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_lm(cfg: ModelConfig, key) -> dict:
    pdt = _pdt(cfg)
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    params: dict = {
        "embed": jax.random.normal(k_emb, (cfg.vocab, cfg.d_model), pdt) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), pdt),
    }
    if not cfg.tied_embeddings:
        params["unembed"] = jax.random.normal(
            k_out, (cfg.d_model, cfg.vocab), pdt) * (cfg.d_model ** -0.5)

    if cfg.layer_types:
        params["layers"] = init_pattern_layers(cfg, k_layers, pdt)
    elif cfg.family == "ssm":
        def one(k):
            km, = jax.random.split(k, 1)
            return {"ln": jnp.ones((cfg.d_model,), pdt),
                    "mamba": init_mamba(km, cfg.d_model, cfg.mamba, pdt)}
        params["layers"] = _stack_init(k_layers, cfg.n_layers, one)
    elif cfg.family == "hybrid":
        params["blocks"] = _init_hybrid_blocks(cfg, k_layers, pdt)
    else:
        def one(k):
            ka, kf = jax.random.split(k)
            lp = {"ln1": jnp.ones((cfg.d_model,), pdt),
                  "ln2": jnp.ones((cfg.d_model,), pdt),
                  "attn": init_attn(ka, cfg, pdt)}
            if cfg.moe is not None:
                lp["moe"] = init_moe(kf, cfg.d_model, cfg.moe, cfg.act, pdt)
                if cfg.moe.dense_residual:
                    lp["mlp"] = init_mlp(jax.random.fold_in(kf, 1),
                                         cfg.d_model, cfg.d_ff, cfg.act, pdt)
            else:
                lp["mlp"] = init_mlp(kf, cfg.d_model, cfg.d_ff, cfg.act, pdt)
            return lp
        params["layers"] = _stack_init(k_layers, cfg.n_layers, one)
    return params


def _init_hybrid_blocks(cfg: ModelConfig, key, pdt):
    """Jamba super-blocks: per block `period` sublayers; index `offset` is
    attention, the rest mamba; odd sublayers use MoE FFN, even use dense."""
    period = cfg.attn_period
    n_blocks = cfg.n_layers // period
    n_mamba = period - 1
    n_moe = period // cfg.moe.every_n_layers
    n_dense = period - n_moe

    def one(k):
        ks = jax.random.split(k, 6)
        return {
            "ln_mix": jnp.ones((period, cfg.d_model), pdt),
            "ln_ffn": jnp.ones((period, cfg.d_model), pdt),
            "mamba": _stack_init(ks[0], n_mamba,
                                 lambda kk: init_mamba(kk, cfg.d_model, cfg.mamba, pdt)),
            "attn": init_attn(ks[1], cfg, pdt),
            "moe": _stack_init(ks[2], n_moe,
                               lambda kk: init_moe(kk, cfg.d_model, cfg.moe, cfg.act, pdt)),
            "mlp": _stack_init(ks[3], n_dense,
                               lambda kk: init_mlp(kk, cfg.d_model, cfg.d_ff, cfg.act, pdt)),
        }
    return _stack_init(key, n_blocks, one)


def lm_param_specs(cfg: ModelConfig) -> dict:
    specs: dict = {"embed": ("vocab", "embed_table"),
                   "final_norm": (None,)}
    if not cfg.tied_embeddings:
        specs["unembed"] = ("embed_table", "vocab")
    if cfg.family == "ssm":
        specs["layers"] = {"ln": ("layers", None),
                           "mamba": mamba_specs()}
    elif cfg.family == "hybrid":
        ms = {k: ("layers", None) + v[1:] for k, v in mamba_specs().items()}
        specs["blocks"] = {
            "ln_mix": ("layers", None, None), "ln_ffn": ("layers", None, None),
            "mamba": ms,
            "attn": {k: ("layers",) + v[1:] for k, v in attn_specs(cfg).items()},
            "moe": {k: ("layers", None) + v[1:] for k, v in moe_specs(cfg.act).items()},
            "mlp": {k: ("layers", None) + v[1:] for k, v in mlp_specs(cfg.act).items()},
        }
    else:
        lp = {"ln1": ("layers", None), "ln2": ("layers", None),
              "attn": attn_specs(cfg)}
        if cfg.moe is not None:
            lp["moe"] = moe_specs(cfg.act)
            if cfg.moe.dense_residual:
                lp["mlp"] = mlp_specs(cfg.act)
        else:
            lp["mlp"] = mlp_specs(cfg.act)
        specs["layers"] = lp
    return specs


# ==================================================================== layers

def _qkv(lp, x, cfg: ModelConfig, cdt, positions):
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ lp["wq"].astype(cdt)
    k = x @ lp["wk"].astype(cdt)
    v = x @ lp["wv"].astype(cdt)
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"].astype(cdt), k + lp["bk"].astype(cdt), v + lp["bv"].astype(cdt)
    # constrain the FLAT head dims (hq*dh, hkv*dh are mesh-divisible for
    # every assigned arch even when head counts are not — see make_rules)
    q = constrain(q, "batch", "seq", "heads").reshape(b, s, hq, dh)
    k = constrain(k, "batch", "seq", "kv_heads").reshape(b, s, hkv, dh)
    v = constrain(v, "batch", "seq", "kv_heads").reshape(b, s, hkv, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, lp["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, lp["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_forward(lp, x, cfg: ModelConfig, cdt, *, impl: str, q_offset=0,
                 block_k: int = 256):
    b, s, _ = x.shape
    positions = q_offset + jnp.arange(s)[None, :]
    q, k, v = _qkv(lp, x, cfg, cdt, positions)
    o = A.attention(q, k, v, causal=True, impl=impl, block_k=block_k)
    o = constrain(o.reshape(b, s, cfg.n_heads * cfg.head_dim),
                  "batch", "seq", "heads")
    out = o @ lp["wo"].astype(cdt)
    return constrain(out, "batch", "seq", None), (k, v)


def attn_decode(lp, x, cfg: ModelConfig, cdt, k_cache, v_cache, cache_len,
                *, sp_axis: Optional[str] = None, kv_scales=None):
    """One decode step against the KV cache.

    ``cache_len`` is a () scalar for lockstep decode, or a (B,) vector for
    per-slot decode (continuous batching): row i writes its new K/V at its
    own position cache_len[i] and attends only its own valid prefix. The
    sequence-parallel path (``sp_axis``) supports scalar lengths only.

    ``kv_scales=(k_scale, v_scale)`` marks an int8 KV cache (codes in
    ``k_cache``/``v_cache``, per-(position, head) f32 scales (B,S,Hkv)):
    the new K/V row is quantized on write at its own position — per-token
    scales, so no other position is ever re-scaled — and the cache is
    dequantized on read. Returns a 5-tuple ``(out, k, v, k_scale,
    v_scale)`` in that mode (3-tuple otherwise); sp decode is float-only.
    """
    b = x.shape[0]
    cl = jnp.asarray(cache_len)
    kv8 = kv_scales is not None
    if kv8 and sp_axis is not None:
        raise NotImplementedError("int8 KV decode: sequence-parallel path "
                                  "is float-only")
    if cl.ndim == 0:
        positions = jnp.full((b, 1), cl, jnp.int32)
    else:
        positions = cl[:, None].astype(jnp.int32)
    q, k, v = _qkv(lp, x, cfg, cdt, positions)
    if kv8:
        k_scale, v_scale = kv_scales
        k, ks_new = A.quantize_kv(k)          # (B,1,Hkv,D) int8, (B,1,Hkv) f32
        v, vs_new = A.quantize_kv(v)
    if cl.ndim == 0:
        k_cache = lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), cl, axis=1)
        v_cache = lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), cl, axis=1)
        if kv8:
            k_scale = lax.dynamic_update_slice_in_dim(k_scale, ks_new, cl,
                                                      axis=1)
            v_scale = lax.dynamic_update_slice_in_dim(v_scale, vs_new, cl,
                                                      axis=1)
    else:
        # per-row scatter at each slot's own length; rows whose length is
        # past the end of the cache (retired slots) simply write nothing
        hot = (jnp.arange(k_cache.shape[1])[None, :] == cl[:, None])
        k_cache = jnp.where(hot[:, :, None, None], k.astype(k_cache.dtype),
                            k_cache)
        v_cache = jnp.where(hot[:, :, None, None], v.astype(v_cache.dtype),
                            v_cache)
        if kv8:
            k_scale = jnp.where(hot[:, :, None], ks_new, k_scale)
            v_scale = jnp.where(hot[:, :, None], vs_new, v_scale)
    if kv8:
        o = A.decode_attention_q8(q, k_cache, v_cache, k_scale, v_scale,
                                  cl + 1)
    elif sp_axis is None:
        o = A.decode_attention(q, k_cache, v_cache, cl + 1)
    else:
        o = _sp_decode(q, k_cache, v_cache, cl + 1, sp_axis)
    out = o.reshape(b, 1, cfg.n_heads * cfg.head_dim) @ lp["wo"].astype(cdt)
    if kv8:
        return out, k_cache, v_cache, k_scale, v_scale
    return out, k_cache, v_cache


def attn_decode_paged(lp, x, cfg: ModelConfig, cdt, k_pool, v_pool,
                      block_table, cache_len, *, scale_pools):
    """One decode step against an int8 paged KV pool (one layer's pools:
    codes (NB, bs, Hkv, D), per-(position, head) f32 scale pools
    ``scale_pools=(k_scale_pool, v_scale_pool)`` (NB, bs, Hkv);
    ``block_table`` (B, nb) int32; ``cache_len`` (B,)).

    Row i quantizes its new K/V on write into the pool page holding its own
    position — page ``block_table[i, cache_len[i] // bs]``, offset
    ``cache_len[i] % bs`` — then attends the dequantized
    gather-by-block-table view, which is bit-identical to the contiguous
    cache (attention.gather_kv_blocks). Retired rows (length 0, zeroed
    table row) write into the reserved garbage page 0, which no live table
    references. Returns ``(out, k_pool, v_pool, k_scale_pool,
    v_scale_pool)``.
    """
    b = x.shape[0]
    bs = k_pool.shape[1]
    cl = jnp.asarray(cache_len)
    positions = cl[:, None].astype(jnp.int32)
    q, k, v = _qkv(lp, x, cfg, cdt, positions)
    bi = jnp.take_along_axis(block_table, (cl // bs)[:, None], axis=1)[:, 0]
    off = cl % bs
    k_scale_pool, v_scale_pool = scale_pools
    k, ks_new = A.quantize_kv(k)          # (B,1,Hkv,D) int8, (B,1,Hkv) f32
    v, vs_new = A.quantize_kv(v)
    k_scale_pool = k_scale_pool.at[bi, off].set(ks_new[:, 0])
    v_scale_pool = v_scale_pool.at[bi, off].set(vs_new[:, 0])
    k_pool = k_pool.at[bi, off].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[bi, off].set(v[:, 0].astype(v_pool.dtype))
    o = A.decode_attention_paged_q8(q, k_pool, v_pool, k_scale_pool,
                                    v_scale_pool, block_table, cl + 1)
    out = o.reshape(b, 1, cfg.n_heads * cfg.head_dim) @ lp["wo"].astype(cdt)
    return out, k_pool, v_pool, k_scale_pool, v_scale_pool


def attn_decode_pages(lp, x, cfg: ModelConfig, cdt, k_pools, v_pools, layer,
                      block_table, cache_len):
    """One decode step of layer ``layer`` against the stacked float pools
    (L, NB, bs, Hkv * D), read and written where they lie.

    Row i writes its new K/V at ``[layer, block_table[i, cache_len[i] //
    bs], cache_len[i] % bs]``, one scatter into the pools, then the paged
    attention kernel reads the pages that hold row i's ``cache_len[i] + 1``
    positions and no other. Retired rows (length 0, zeroed table row)
    write into the reserved garbage page 0, which no live table references.
    Returns ``(out, k_pools, v_pools)``.
    """
    b = x.shape[0]
    bs = k_pools.shape[2]
    cl = jnp.asarray(cache_len)
    q, k, v = _qkv(lp, x, cfg, cdt, cl[:, None].astype(jnp.int32))
    bi = jnp.take_along_axis(block_table, (cl // bs)[:, None], axis=1)[:, 0]
    off = cl % bs
    k_pools = k_pools.at[layer, bi, off].set(
        k.reshape(b, -1).astype(k_pools.dtype))
    v_pools = v_pools.at[layer, bi, off].set(
        v.reshape(b, -1).astype(v_pools.dtype))
    o = paged_attention(q[:, 0], k_pools, v_pools, layer, block_table, cl + 1)
    out = o.reshape(b, 1, cfg.n_heads * cfg.head_dim) @ lp["wo"].astype(cdt)
    return out, k_pools, v_pools


def _sp_decode(q, k_cache, v_cache, n_valid, axis: str):
    """Sequence-parallel decode: KV cache sharded over `axis` along seq;
    batch stays on its DP axes. Per-shard flash statistics are combined with
    a psum whose payload is O(heads · head_dim), not O(S)."""
    from jax.experimental.shard_map import shard_map
    from repro.parallel.sharding import active_rules, current_mesh
    mesh = current_mesh()
    if mesh is None or mesh.shape.get(axis, 1) == 1:
        return A.decode_attention(q, k_cache, v_cache, n_valid)
    rules = active_rules()
    s_loc = k_cache.shape[1] // mesh.shape[axis]
    q_spec = rules.spec("batch", None, None, None)
    kv_spec = rules.spec("batch", "kv_seq", None, None)

    def body(qb, kb, vb, nv):
        i = lax.axis_index(axis)
        pos = i * s_loc + jnp.arange(s_loc)
        m, l, o = A.decode_attention_partial(qb, kb, vb, pos < nv)
        o = A.sp_combine(m, l, o, axis)
        b = qb.shape[0]
        return jnp.moveaxis(o, 3, 1).reshape(b, 1, -1, qb.shape[-1]).astype(qb.dtype)

    return shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, jax.sharding.PartitionSpec()),
        out_specs=q_spec, check_rep=False)(q, k_cache, v_cache, n_valid)


def ffn_forward(lp, x, cfg: ModelConfig, cdt, precision: str = "float"):
    """``precision``: "float" (default) or the serve engine's integer modes
    "int8" / "int8-xla" — those route the FFN matmuls through the quantized
    kernel layer (blocks.qmlp); the layer params must carry a "qmlp" tree
    (serve.Engine adds it at init)."""
    if precision != "float":
        if "qmlp" not in lp:
            raise ValueError(
                f"precision={precision!r} needs quantized FFN params; run "
                "blocks.quantize_mlp_params (serve.Engine does this when "
                "ServeConfig.precision != 'float')")
        from .blocks import qmlp
        return qmlp(x, lp["qmlp"], cfg.act, cdt,
                    method="xla" if precision == "int8-xla" else "pallas")
    if cfg.moe is not None and "moe" in lp:
        y = moe_ffn(x, lp["moe"], cfg.moe, cfg.act, cdt)
        if cfg.moe.dense_residual:
            y = y + mlp(x, lp["mlp"], cfg.act, cdt)
        return y
    return mlp(x, lp["mlp"], cfg.act, cdt)


def dense_layer(h, lp, cfg: ModelConfig, cdt, *, impl: str):
    a, _ = attn_forward(lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps),
                        cfg, cdt, impl=impl)
    h = h + a
    f = ffn_forward(lp, rmsnorm(h, lp["ln2"], cfg.norm_eps), cfg, cdt)
    # residual-stream constraint: the scan carry is the per-layer activation
    # checkpoint; sharding its d_model over "model" (when rules say so)
    # divides the dominant training-memory term by the TP degree
    return constrain(h + f, "batch", "seq", "d_model_act")


def ssm_layer(h, lp, cfg: ModelConfig, cdt, conv_method="auto"):
    y = mamba_forward(lp["mamba"], rmsnorm(h, lp["ln"], cfg.norm_eps),
                      cfg.mamba, cdt, conv_method=conv_method)
    return constrain(h + y, "batch", "seq", "d_model_act")


def hybrid_block(h, bp, cfg: ModelConfig, cdt, *, impl: str):
    period = cfg.attn_period
    m_idx = moe_idx = mlp_idx = 0
    for i in range(period):
        x = rmsnorm(h, bp["ln_mix"][i], cfg.norm_eps)
        if i == cfg.attn_offset:
            a, _ = attn_forward(bp["attn"], x, cfg, cdt, impl=impl)
            h = h + a
        else:
            lp = jax.tree_util.tree_map(lambda v, j=m_idx: v[j], bp["mamba"])
            h = h + mamba_forward(lp, x, cfg.mamba, cdt)
            m_idx += 1
        f_in = rmsnorm(h, bp["ln_ffn"][i], cfg.norm_eps)
        if i % cfg.moe.every_n_layers == 1:
            mp = jax.tree_util.tree_map(lambda v, j=moe_idx: v[j], bp["moe"])
            h = h + moe_ffn(f_in, mp, cfg.moe, cfg.act, cdt)
            moe_idx += 1
        else:
            dp = jax.tree_util.tree_map(lambda v, j=mlp_idx: v[j], bp["mlp"])
            h = h + mlp(f_in, dp, cfg.act, cdt)
            mlp_idx += 1
    return constrain(h, "batch", None, "d_model_act")


# =================================================================== forward

def _remat(fn, mode: str):
    if mode == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            prevent_cse=False)
    return fn


def embed_tokens(params, tokens, cfg: ModelConfig, cdt):
    e = params["embed"][tokens]
    return e.astype(cdt)


def unembed(params, h, cfg: ModelConfig):
    w = params["embed"].T if cfg.tied_embeddings else params["unembed"]
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    return constrain(logits, "batch", None, "vocab")


def forward_hidden(params, tokens, cfg: ModelConfig, *, embeds=None,
                   attn_impl: str = "full", remat: str = "full"):
    """Final hidden states (post final-norm), before the unembedding."""
    cdt = _cdt(cfg)
    h = embed_tokens(params, tokens, cfg, cdt)
    if embeds is not None:
        h = jnp.concatenate([embeds.astype(cdt), h], axis=1)
    h = constrain(h, "batch", "seq", None)

    if cfg.layer_types:
        h = pattern_stack(params, h, cfg, cdt, impl=attn_impl)[0]
    elif cfg.family == "hybrid":
        body = _remat(lambda hh, bp: (hybrid_block(hh, bp, cfg, cdt,
                                                   impl=attn_impl), None), remat)
        h, _ = lax.scan(body, h, params["blocks"])
    elif cfg.family == "ssm":
        body = _remat(lambda hh, lp: (ssm_layer(hh, lp, cfg, cdt), None), remat)
        h, _ = lax.scan(body, h, params["layers"])
    else:
        body = _remat(lambda hh, lp: (dense_layer(hh, lp, cfg, cdt,
                                                  impl=attn_impl), None), remat)
        h, _ = lax.scan(body, h, params["layers"])

    return rmsnorm(h, params["final_norm"], cfg.norm_eps)


def forward(params, tokens, cfg: ModelConfig, *, embeds=None,
            attn_impl: str = "full", remat: str = "full"):
    """tokens: (B, S_txt) int32; embeds (vlm/audio stub): (B, P, d_model)."""
    h = forward_hidden(params, tokens, cfg, embeds=embeds,
                       attn_impl=attn_impl, remat=remat)
    return unembed(params, h, cfg)


def lm_loss(params, batch, cfg: ModelConfig, *, attn_impl="full", remat="full",
            z_loss: float = 1e-4, loss_chunk: int = 512):
    from .blocks import chunked_softmax_ce
    tokens = batch["tokens"]
    h = forward_hidden(params, tokens[:, :-1], cfg,
                       embeds=batch.get("embeds"), attn_impl=attn_impl,
                       remat=remat)
    n_img = 0 if batch.get("embeds") is None else batch["embeds"].shape[1]
    w = params["embed"].T if cfg.tied_embeddings else params["unembed"]
    return chunked_softmax_ce(h[:, n_img:], w, tokens[:, 1:],
                              chunk=loss_chunk, z_loss=z_loss)


# ==================================================================== decode

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    if cfg.layer_types:
        la = cfg.n_attn_layers
        return {"k": jnp.zeros((la, batch, max_len, hkv, dh), dtype),
                "v": jnp.zeros((la, batch, max_len, hkv, dh), dtype),
                **init_pattern_state(cfg, batch, dtype),
                "len": jnp.zeros((), jnp.int32)}
    if cfg.family == "ssm":
        st = mamba_init_state(cfg.d_model, cfg.mamba, batch)
        return {"conv": jnp.zeros((cfg.n_layers,) + st["conv"].shape, dtype),
                "ssm": jnp.zeros((cfg.n_layers,) + st["ssm"].shape, jnp.float32),
                "len": jnp.zeros((), jnp.int32)}
    if cfg.family == "hybrid":
        nb = cfg.n_layers // cfg.attn_period
        nm = cfg.attn_period - 1
        st = mamba_init_state(cfg.d_model, cfg.mamba, batch)
        return {"k": jnp.zeros((nb, batch, max_len, hkv, dh), dtype),
                "v": jnp.zeros((nb, batch, max_len, hkv, dh), dtype),
                "conv": jnp.zeros((nb, nm) + st["conv"].shape, dtype),
                "ssm": jnp.zeros((nb, nm) + st["ssm"].shape, jnp.float32),
                "len": jnp.zeros((), jnp.int32)}
    return {"k": jnp.zeros((cfg.n_layers, batch, max_len, hkv, dh), dtype),
            "v": jnp.zeros((cfg.n_layers, batch, max_len, hkv, dh), dtype),
            "len": jnp.zeros((), jnp.int32)}


def cache_specs(cfg: ModelConfig):
    """Logical sharding for the cache (SP shards kv_seq over data)."""
    if cfg.family == "ssm":
        return {"conv": (None, "batch", None, "d_inner"),
                "ssm": (None, "batch", "d_inner", None), "len": ()}
    kv = (None, "batch", "kv_seq", "kv_heads", None)
    if cfg.family == "hybrid":
        return {"k": kv, "v": kv,
                "conv": (None, None, "batch", None, "d_inner"),
                "ssm": (None, None, "batch", "d_inner", None), "len": ()}
    return {"k": kv, "v": kv, "len": ()}


def decode_step(params, token, cache, cfg: ModelConfig, *,
                sp_axis: Optional[str] = None, precision: str = "float"):
    """One-token serve step. token: (B, 1) int32. ``precision`` "int8" /
    "int8-xla" runs the FFN matmuls integer-only (see ffn_forward)."""
    if precision != "float" and cfg.family in ("ssm", "hybrid"):
        raise NotImplementedError(
            "integer-FFN decode only covers attention-family dense MLPs")
    kv8 = "k_scale" in cache
    if kv8 and cfg.family in ("ssm", "hybrid"):
        raise NotImplementedError(
            "int8 KV decode only covers attention-family dense caches")
    cdt = _cdt(cfg)
    h = embed_tokens(params, token, cfg, cdt)
    clen = cache["len"]
    new_cache = dict(cache)

    if cfg.layer_types:
        h, state = pattern_decode(params, h, cache, cfg, cdt)
        new_cache.update(state)
    elif cfg.family == "ssm":
        def body(hh, xs):
            lp, conv, ssm = xs
            x = rmsnorm(hh, lp["ln"], cfg.norm_eps)
            y, st = mamba_decode_step(lp["mamba"], x, {"conv": conv, "ssm": ssm},
                                      cfg.mamba, cdt)
            return hh + y, (st["conv"], st["ssm"])
        h, (conv_new, ssm_new) = lax.scan(body, h,
                                          (params["layers"], cache["conv"],
                                           cache["ssm"]))
        new_cache.update(conv=conv_new, ssm=ssm_new)
    elif cfg.family == "hybrid":
        def body(hh, xs):
            bp, kc, vc, conv, ssm = xs
            period = cfg.attn_period
            m_idx = moe_idx = mlp_idx = 0
            convs, ssms = [], []
            for i in range(period):
                x = rmsnorm(hh, bp["ln_mix"][i], cfg.norm_eps)
                if i == cfg.attn_offset:
                    a, kc, vc = attn_decode(bp["attn"], x, cfg, cdt, kc, vc,
                                            clen, sp_axis=sp_axis)
                    hh = hh + a
                else:
                    lp = jax.tree_util.tree_map(lambda v, j=m_idx: v[j], bp["mamba"])
                    y, st = mamba_decode_step(
                        lp, x, {"conv": conv[m_idx], "ssm": ssm[m_idx]},
                        cfg.mamba, cdt)
                    hh = hh + y
                    convs.append(st["conv"]); ssms.append(st["ssm"])
                    m_idx += 1
                f_in = rmsnorm(hh, bp["ln_ffn"][i], cfg.norm_eps)
                if i % cfg.moe.every_n_layers == 1:
                    mp = jax.tree_util.tree_map(lambda v, j=moe_idx: v[j], bp["moe"])
                    hh = hh + moe_ffn(f_in, mp, cfg.moe, cfg.act, cdt)
                    moe_idx += 1
                else:
                    dp = jax.tree_util.tree_map(lambda v, j=mlp_idx: v[j], bp["mlp"])
                    hh = hh + mlp(f_in, dp, cfg.act, cdt)
                    mlp_idx += 1
            return hh, (kc, vc, jnp.stack(convs), jnp.stack(ssms))
        h, (k_new, v_new, conv_new, ssm_new) = lax.scan(
            body, h, (params["blocks"], cache["k"], cache["v"],
                      cache["conv"], cache["ssm"]))
        new_cache.update(k=k_new, v=v_new, conv=conv_new, ssm=ssm_new)
    elif "block_table" in cache:
        # paged pools: float (L, NB, bs, Hkv*D), or int8 (L, NB, bs, Hkv, D)
        # + (L, NB, bs, Hkv) scales; the (B, nb) block table is shared by
        # every layer (same logical layout, per-layer pools indexed by the
        # same page ids)
        bt = cache["block_table"]
        if sp_axis is not None:
            raise NotImplementedError("paged KV decode: sequence-parallel "
                                      "path is contiguous-only")
        if kv8:
            def body(hh, xs):
                lp, kp, vp, ksp, vsp = xs
                x = rmsnorm(hh, lp["ln1"], cfg.norm_eps)
                a, kp, vp, ksp, vsp = attn_decode_paged(
                    lp["attn"], x, cfg, cdt, kp, vp, bt, clen,
                    scale_pools=(ksp, vsp))
                hh = hh + a
                f = ffn_forward(lp, rmsnorm(hh, lp["ln2"], cfg.norm_eps),
                                cfg, cdt, precision=precision)
                return hh + f, (kp, vp, ksp, vsp)
            h, (k_new, v_new, ks_new, vs_new) = lax.scan(
                body, h, (params["layers"], cache["k"], cache["v"],
                          cache["k_scale"], cache["v_scale"]))
            new_cache.update(k=k_new, v=v_new, k_scale=ks_new, v_scale=vs_new)
        else:
            # the stacked pools ride in the carry, never sliced per layer:
            # each layer scatters its new K/V into them and the kernel
            # reads its pages at the layer index
            def body(carry, xs):
                hh, kp, vp = carry
                lp, layer = xs
                x = rmsnorm(hh, lp["ln1"], cfg.norm_eps)
                a, kp, vp = attn_decode_pages(lp["attn"], x, cfg, cdt,
                                              kp, vp, layer, bt, clen)
                hh = hh + a
                f = ffn_forward(lp, rmsnorm(hh, lp["ln2"], cfg.norm_eps),
                                cfg, cdt, precision=precision)
                return (hh + f, kp, vp), None
            (h, k_new, v_new), _ = lax.scan(
                body, (h, cache["k"], cache["v"]),
                (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
            new_cache.update(k=k_new, v=v_new)
    elif kv8:
        def body(hh, xs):
            lp, kc, vc, ks, vs = xs
            x = rmsnorm(hh, lp["ln1"], cfg.norm_eps)
            a, kc, vc, ks, vs = attn_decode(lp["attn"], x, cfg, cdt, kc, vc,
                                            clen, sp_axis=sp_axis,
                                            kv_scales=(ks, vs))
            hh = hh + a
            f = ffn_forward(lp, rmsnorm(hh, lp["ln2"], cfg.norm_eps), cfg, cdt,
                            precision=precision)
            return hh + f, (kc, vc, ks, vs)
        h, (k_new, v_new, ks_new, vs_new) = lax.scan(
            body, h, (params["layers"], cache["k"], cache["v"],
                      cache["k_scale"], cache["v_scale"]))
        new_cache.update(k=k_new, v=v_new, k_scale=ks_new, v_scale=vs_new)
    else:
        def body(hh, xs):
            lp, kc, vc = xs
            x = rmsnorm(hh, lp["ln1"], cfg.norm_eps)
            a, kc, vc = attn_decode(lp["attn"], x, cfg, cdt, kc, vc, clen,
                                    sp_axis=sp_axis)
            hh = hh + a
            f = ffn_forward(lp, rmsnorm(hh, lp["ln2"], cfg.norm_eps), cfg, cdt,
                            precision=precision)
            return hh + f, (kc, vc)
        h, (k_new, v_new) = lax.scan(body, h,
                                     (params["layers"], cache["k"], cache["v"]))
        new_cache.update(k=k_new, v=v_new)

    new_cache["len"] = clen + 1
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return unembed(params, h, cfg), new_cache


def prefill(params, tokens, cfg: ModelConfig, max_len: int, *, embeds=None,
            attn_impl: str = "flash", prompt_lens=None,
            precision: str = "float", attn_block_k: int = 256):
    """Run the prompt, build the cache, return (last_logits, cache).

    For attention families the per-layer K/V come out of the layer scan; for
    ssm/hybrid the states come from a chunk-scan epilogue (decode-step replay
    of the last conv window + final ssm state).

    With ``prompt_lens`` (a (B,) int32 vector) the batch is RIGHT-padded:
    row i's real tokens occupy positions [0, prompt_lens[i]) — causality
    already keeps real tokens from attending the trailing pads, pad K/V land
    at positions >= prompt_lens[i] where the per-slot decode mask (and the
    next writes) neutralize them, and rope positions stay 0..len-1 exactly
    as in an unpadded prefill. Logits are gathered at each row's last real
    position and ``cache["len"]`` becomes the per-row length vector (the
    slot-cache convention — see models/api.init_slot_cache). Right-padding
    is only exact for attention families; ssm/hybrid recurrences fold every
    position into their state, so callers must pass exact lengths
    (prompt_lens[i] == S) for those families.

    ``attn_block_k`` pins the flash-attention KV-block size. Serving passes
    a FIXED value across every prefill bucket: with a constant block size a
    prefix row's K/V are bitwise independent of how far the bucket extends
    past it (trailing fully-masked KV blocks are exact no-ops in the online
    softmax), which is what makes hash-based prefix reuse exact — see
    :func:`prefill_suffix`.
    """
    if precision != "float" and cfg.family in ("ssm", "hybrid"):
        raise NotImplementedError(
            "integer-FFN prefill only covers attention-family dense MLPs")
    cdt = _cdt(cfg)
    b = tokens.shape[0]
    s_prompt = tokens.shape[1] + (0 if embeds is None else embeds.shape[1])
    # a pattern stack keeps its cache in the compute dtype
    cache = init_cache(cfg, b, max_len,
                       cdt if cfg.layer_types else jnp.bfloat16)
    h = embed_tokens(params, tokens, cfg, cdt)
    if embeds is not None:
        h = jnp.concatenate([embeds.astype(cdt), h], axis=1)

    if cfg.layer_types:
        h, ks, vs, state = pattern_stack(
            params, h, cfg, cdt, impl=attn_impl, block_k=attn_block_k,
            lengths=prompt_lens)
        cache.update(k=_stack_kv(ks, max_len, cache["k"]),
                     v=_stack_kv(vs, max_len, cache["v"]), **state)
    elif cfg.family == "ssm":
        def body(hh, lp):
            x = rmsnorm(hh, lp["ln"], cfg.norm_eps)
            y, st = _mamba_forward_with_state(lp["mamba"], x, cfg.mamba, cdt)
            return hh + y, st
        h, states = lax.scan(body, h, params["layers"])
        cache.update(conv=states["conv"].astype(cache["conv"].dtype),
                     ssm=states["ssm"])
    elif cfg.family == "hybrid":
        def body(hh, bp):
            hh, kvs = _hybrid_block_with_state(hh, bp, cfg, cdt, attn_impl,
                                               max_len)
            return hh, kvs
        h, st = lax.scan(body, h, params["blocks"])
        cache.update(k=st["k"].astype(cache["k"].dtype),
                     v=st["v"].astype(cache["v"].dtype),
                     conv=st["conv"].astype(cache["conv"].dtype),
                     ssm=st["ssm"])
    else:
        def body(hh, lp):
            x = rmsnorm(hh, lp["ln1"], cfg.norm_eps)
            a, (k, v) = attn_forward(lp["attn"], x, cfg, cdt, impl=attn_impl,
                                     block_k=attn_block_k)
            hh = hh + a
            f = ffn_forward(lp, rmsnorm(hh, lp["ln2"], cfg.norm_eps), cfg, cdt,
                            precision=precision)
            k = _pad_seq(k, max_len).astype(cache["k"].dtype)
            v = _pad_seq(v, max_len).astype(cache["v"].dtype)
            return hh + f, (k, v)
        h, (ks, vs) = lax.scan(body, h, params["layers"])
        cache.update(k=ks, v=vs)

    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    if prompt_lens is None:
        cache["len"] = jnp.array(s_prompt, jnp.int32)
        return unembed(params, h[:, -1:], cfg), cache
    pl = jnp.asarray(prompt_lens, jnp.int32)
    cache["len"] = pl
    h_last = jnp.take_along_axis(h, (pl - 1)[:, None, None], axis=1)
    return unembed(params, h_last, cfg), cache


def _stack_kv(per_layer, max_len, like):
    """Attention layers' (B, S, Hkv, D) K or V, padded to ``max_len`` and
    stacked in layer order (``like``: the empty cache leaf)."""
    if not per_layer:
        return like
    return jnp.stack([_pad_seq(x, max_len) for x in per_layer]).astype(
        like.dtype)


def _pad_seq(x, max_len):
    pad = max_len - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else x


def _mamba_forward_with_state(p, x, m, cdt):
    """mamba_forward that also returns final {conv, ssm} state."""
    from .mamba import mamba_scan, _resolve_conv_method
    from repro.kernels.ops import causal_conv1d
    rank = p["dt_proj"].shape[0]
    n = p["A_log"].shape[-1]
    xz = x @ p["in_proj"].astype(cdt)
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_in = constrain(x_in, "batch", None, "d_inner")
    x_c = causal_conv1d(x_in, p["conv_w"].astype(cdt),
                        method=_resolve_conv_method("auto"))
    x_c = jax.nn.silu(x_c + p["conv_b"].astype(cdt))
    dbc = x_c @ p["x_proj"].astype(cdt)
    dt_low, b_t, c_t = jnp.split(dbc, [rank, rank + n], axis=-1)
    dt = jax.nn.softplus(dt_low @ p["dt_proj"].astype(cdt) + p["dt_bias"].astype(cdt))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, h_last = mamba_scan(x_c, dt, A, b_t, c_t)
    y = y + p["D"].astype(cdt) * x_c
    y = y * jax.nn.silu(z)
    out = y @ p["out_proj"].astype(cdt)
    k = p["conv_w"].shape[0]
    conv_state = x_in[:, -(k - 1):, :]
    return out, {"conv": conv_state, "ssm": h_last}


def _hybrid_block_with_state(h, bp, cfg, cdt, attn_impl, max_len):
    period = cfg.attn_period
    m_idx = moe_idx = mlp_idx = 0
    convs, ssms, kv = [], [], None
    for i in range(period):
        x = rmsnorm(h, bp["ln_mix"][i], cfg.norm_eps)
        if i == cfg.attn_offset:
            a, (k, v) = attn_forward(bp["attn"], x, cfg, cdt, impl=attn_impl)
            h = h + a
            kv = (_pad_seq(k, max_len), _pad_seq(v, max_len))
        else:
            lp = jax.tree_util.tree_map(lambda v_, j=m_idx: v_[j], bp["mamba"])
            y, st = _mamba_forward_with_state(lp, x, cfg.mamba, cdt)
            h = h + y
            convs.append(st["conv"]); ssms.append(st["ssm"])
            m_idx += 1
        f_in = rmsnorm(h, bp["ln_ffn"][i], cfg.norm_eps)
        if i % cfg.moe.every_n_layers == 1:
            mp = jax.tree_util.tree_map(lambda v_, j=moe_idx: v_[j], bp["moe"])
            h = h + moe_ffn(f_in, mp, cfg.moe, cfg.act, cdt)
            moe_idx += 1
        else:
            dp = jax.tree_util.tree_map(lambda v_, j=mlp_idx: v_[j], bp["mlp"])
            h = h + mlp(f_in, dp, cfg.act, cdt)
            mlp_idx += 1
    return h, {"k": kv[0], "v": kv[1],
               "conv": jnp.stack(convs), "ssm": jnp.stack(ssms)}


def prefill_suffix(params, tokens, prefix_k, prefix_v, prefix_len: int,
                   cfg: ModelConfig, *, suffix_lens, attn_impl: str = "flash",
                   attn_block_k: int = 256, precision: str = "float"):
    """Chunked prefill against a cached prefix: compute only the suffix.

    The prefix-cache hit path of paged serving — the leading ``prefix_len``
    positions' K/V already live in the block pool (computed once by the
    donor request), so only ``tokens`` (the right-padded suffix, occupying
    global positions ``prefix_len .. prefix_len + S_sfx - 1``) runs through
    the layers. Per layer, the suffix queries attend the concatenation of
    the gathered prefix K/V and the fresh suffix K/V with
    ``q_offset=prefix_len``.

    Bit-exactness contract: causality makes a prefix position's hidden
    state independent of the suffix, and a FIXED ``attn_block_k`` (dividing
    both ``prefix_len`` and the suffix bucket) makes the flash KV-block
    schedule of every suffix row identical to the full-prompt prefill's —
    so the returned logits and suffix K/V are bitwise what a full prefill
    of the whole prompt would have produced (tested in test_paged.py).

    tokens: (B, S_sfx) int32; prefix_k/v: (L, B, prefix_len, Hkv, D), or
    (L, B, prefix_len, Hkv * D) as a float paged pool holds them, in the
    compute dtype; suffix_lens: (B,) int32 real suffix lengths. Returns
    ``(last_logits, k_sfx, v_sfx)`` with k/v_sfx (L, B, S_sfx, Hkv, D) —
    the caller scatters them into pool pages. Attention families with
    dense-layer stacks only (the paged engine's admission gate).
    """
    if cfg.family in ("ssm", "hybrid", "encdec") or cfg.layer_types:
        raise NotImplementedError(
            "prefill_suffix covers attention-family dense layer stacks only")
    cdt = _cdt(cfg)
    s = tokens.shape[1]
    h = embed_tokens(params, tokens, cfg, cdt)

    def body(hh, xs):
        lp, pk, pv = xs
        x = rmsnorm(hh, lp["ln1"], cfg.norm_eps)
        positions = prefix_len + jnp.arange(s)[None, :]
        q, k, v = _qkv(lp["attn"], x, cfg, cdt, positions)
        # paged pools hand the prefix over with the heads flattened
        pk = pk.reshape(pk.shape[:2] + k.shape[2:])
        pv = pv.reshape(pv.shape[:2] + v.shape[2:])
        k_cat = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        v_cat = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        o = A.attention(q, k_cat, v_cat, causal=True, impl=attn_impl,
                        q_offset=prefix_len, block_k=attn_block_k)
        b = x.shape[0]
        o = constrain(o.reshape(b, s, cfg.n_heads * cfg.head_dim),
                      "batch", "seq", "heads")
        a = constrain(o @ lp["attn"]["wo"].astype(cdt), "batch", "seq", None)
        hh = hh + a
        f = ffn_forward(lp, rmsnorm(hh, lp["ln2"], cfg.norm_eps), cfg, cdt,
                        precision=precision)
        return hh + f, (k, v)

    h, (ks, vs) = lax.scan(body, h, (params["layers"], prefix_k, prefix_v))
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    sl = jnp.asarray(suffix_lens, jnp.int32)
    h_last = jnp.take_along_axis(h, (sl - 1)[:, None, None], axis=1)
    return unembed(params, h_last, cfg), ks, vs


# ============================================================ pattern stacks
#
# A config with ``layer_types`` (LFM2) runs an unrolled list of per-layer
# parameter dicts. Each layer is pre-norm with residuals: its operator (a
# gated short convolution, or GQA attention with per-head q/k RMSNorm),
# then its FFN, a dense SwiGLU for the first ``n_dense_layers`` and a
# dropless MoE (``moe_experts`` kernel) after. The decode cache holds KV
# for the attention layers only, stacked in their order; ``conv``, the
# short convolutions' states (L_conv, B, K - 1, d); and ``moe_counts``
# (L_moe, E), the assignments each expert took in the last call, which the
# engine reads after each round.


def init_pattern_layers(cfg: ModelConfig, key, pdt) -> list:
    d = cfg.d_model
    layers = []
    for i, (kind, k) in enumerate(zip(cfg.layer_kinds(),
                                      jax.random.split(key, cfg.n_layers))):
        ko, kf = jax.random.split(k)
        lp = {"ln1": jnp.ones((d,), pdt), "ln2": jnp.ones((d,), pdt)}
        if kind == "conv":
            lp["conv"] = init_shortconv(ko, d, cfg.conv_kernel, pdt)
        else:
            lp["attn"] = init_attn(ko, cfg, pdt)
        if cfg.moe is None or i < cfg.n_dense_layers:
            lp["mlp"] = init_mlp(kf, d, cfg.d_ff, cfg.act, pdt)
        else:
            lp["moe"] = init_moe_dropless(kf, d, cfg.moe, pdt)
        layers.append(lp)
    return layers


def init_pattern_state(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    """The per-slot state beside KV: ``conv`` (L_conv, B, K - 1, d) and,
    with experts, ``moe_counts`` (L_moe, E) int32."""
    out = {"conv": jnp.zeros((cfg.n_conv_layers, batch,
                              max(cfg.conv_kernel - 1, 0), cfg.d_model),
                             dtype)}
    if cfg.n_moe_layers:
        out["moe_counts"] = jnp.zeros(
            (cfg.n_moe_layers, cfg.moe.num_experts), jnp.int32)
    return out


def _pattern_ffn(lp, x, cfg: ModelConfig, cdt, counts: list):
    if "moe" in lp:
        y, c = moe_ffn_dropless(x, lp["moe"], cfg.moe, cdt)
        counts.append(c)
        return y
    return mlp(x, lp["mlp"], cfg.act, cdt)


def _pattern_state(states, counts, like_conv):
    out = {"conv": jnp.stack(states).astype(like_conv.dtype)
           if states else like_conv}
    if counts:
        out["moe_counts"] = jnp.stack(counts)
    return out


def pattern_stack(params, h, cfg: ModelConfig, cdt, *, impl: str,
                  block_k: int = 256, lengths=None):
    """The whole prompt through a pattern stack: returns the last layer's
    hidden states (before the final norm), each attention layer's K and V,
    and the state
    (``conv`` at each row's last ``K - 1`` real positions, ``lengths``
    (B,) int32 or the full length; ``moe_counts``)."""
    ks, vs, states, counts = [], [], [], []
    for kind, lp in zip(cfg.layer_kinds(), params["layers"]):
        x = rmsnorm(h, lp["ln1"], cfg.norm_eps)
        if kind == "conv":
            y, st = shortconv_forward(lp["conv"], x, cdt, lengths)
            states.append(st)
        else:
            y, (k, v) = attn_forward(lp["attn"], x, cfg, cdt, impl=impl,
                                     block_k=block_k)
            ks.append(k)
            vs.append(v)
        h = h + y
        h = h + _pattern_ffn(lp, rmsnorm(h, lp["ln2"], cfg.norm_eps), cfg,
                             cdt, counts)
    like = init_pattern_state(cfg, h.shape[0], cdt)["conv"]
    return h, ks, vs, _pattern_state(states, counts, like)


def pattern_decode(params, h, cache, cfg: ModelConfig, cdt):
    """One token through a pattern stack against its paged cache (float
    pools (L_attn, NB, bs, Hkv * D), read in place by the paged attention
    kernel). Returns the last layer's hidden state and the cache leaves it
    changed."""
    if "block_table" not in cache:
        raise NotImplementedError("pattern stacks decode from a paged cache")
    k, v, conv = cache["k"], cache["v"], cache["conv"]
    states, counts = [], []
    ia = ic = 0
    for kind, lp in zip(cfg.layer_kinds(), params["layers"]):
        x = rmsnorm(h, lp["ln1"], cfg.norm_eps)
        if kind == "conv":
            y, st = shortconv_step(lp["conv"], x, conv[ic], cdt)
            states.append(st)
            ic += 1
        else:
            y, k, v = attn_decode_pages(lp["attn"], x, cfg, cdt, k, v, ia,
                                        cache["block_table"], cache["len"])
            ia += 1
        h = h + y
        h = h + _pattern_ffn(lp, rmsnorm(h, lp["ln2"], cfg.norm_eps), cfg,
                             cdt, counts)
    return h, dict(_pattern_state(states, counts, conv), k=k, v=v)
