"""Unified model API: init / loss / prefill / decode / input_specs per family.

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every
model input of that (arch x shape) cell — the dry-run lowers against these
without allocating anything. Modality frontends (vlm/audio) are STUBS: the
specs include precomputed patch/frame embeddings, per the assignment.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig

from . import attention as A
from . import encdec as E
from . import transformer as T


def init_params(cfg: ModelConfig, key):
    if cfg.family == "encdec":
        return E.init_encdec(cfg, key)
    return T.init_lm(cfg, key)


def param_specs(cfg: ModelConfig):
    if cfg.family == "encdec":
        return E.encdec_param_specs(cfg)
    return T.lm_param_specs(cfg)


def loss_fn(cfg: ModelConfig, *, attn_impl="full", remat="full"):
    if cfg.family == "encdec":
        return functools.partial(E.encdec_loss, cfg=cfg, attn_impl=attn_impl,
                                 remat=remat)
    return functools.partial(T.lm_loss, cfg=cfg, attn_impl=attn_impl,
                             remat=remat)


def prefill_fn(cfg: ModelConfig, max_len: int, *, attn_impl="flash",
               precision: str = "float", attn_block_k: int = 256):
    if cfg.family == "encdec":
        if precision != "float":
            raise NotImplementedError("integer-FFN serve: encdec unsupported")
        def fn(params, batch):
            return E.encdec_prefill(params, batch["frames"], batch["tokens"],
                                    cfg, max_len, attn_impl=attn_impl)
    else:
        def fn(params, batch):
            return T.prefill(params, batch["tokens"], cfg, max_len,
                             embeds=batch.get("embeds"), attn_impl=attn_impl,
                             prompt_lens=batch.get("prompt_lens"),
                             precision=precision, attn_block_k=attn_block_k)
    return fn


def prefill_suffix_fn(cfg: ModelConfig, *, attn_impl="flash",
                      attn_block_k: int = 256, precision: str = "float"):
    """Prefix-cache hit path: run only the suffix of a prompt against
    gathered prefix K/V (see transformer.prefill_suffix). The prefix length
    is taken from ``batch["prefix_k"].shape[2]`` — jit once per
    (prefix_len, suffix_bucket) pair."""
    if cfg.family in ("ssm", "hybrid", "encdec"):
        raise NotImplementedError(
            "prefill_suffix covers attention-family dense layer stacks only")

    def fn(params, batch):
        pk = batch["prefix_k"]
        return T.prefill_suffix(params, batch["tokens"], pk,
                                batch["prefix_v"], pk.shape[2], cfg,
                                suffix_lens=batch["suffix_lens"],
                                attn_impl=attn_impl,
                                attn_block_k=attn_block_k,
                                precision=precision)
    return fn


def decode_fn(cfg: ModelConfig, *, sp_axis: Optional[str] = None,
              precision: str = "float"):
    if cfg.family == "encdec":
        if precision != "float":
            raise NotImplementedError("integer-FFN serve: encdec unsupported")
        return functools.partial(E.encdec_decode_step, cfg=cfg, sp_axis=sp_axis)
    return functools.partial(T.decode_step, cfg=cfg, sp_axis=sp_axis,
                             precision=precision)


def decode_pick_fn(cfg: ModelConfig, *, sp_axis: Optional[str] = None,
                   precision: str = "float"):
    """:func:`decode_fn` that also picks each row's greedy token on the
    device: ``(params, token, cache) -> (ids, logits, cache)`` with ``ids``
    the (B,) int32 argmax of the last position's logits (ties to the first
    index, as ``np.argmax``). The step and its float logits are unchanged;
    a server that samples greedily copies back only ``ids``."""
    step = decode_fn(cfg, sp_axis=sp_axis, precision=precision)

    def fn(params, token, cache):
        logits, cache = step(params, token, cache)
        ids = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return ids, logits, cache
    return fn


# the leaves the serving step reads only through .astype(compute_dtype)
# (the audit is in serving_params' docstring)
_STEP_CAST_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "bq", "bk", "bv",
    "w_gate", "w_up", "w_down",
    "in_proj", "out_proj", "conv_w",
    "conv_b", "x_proj", "dt_proj", "dt_bias", "D"})


def serving_params(cfg: ModelConfig, params):
    """``params`` as the serving step reads it: every leaf the step reads
    only through ``.astype(cfg.compute_dtype)`` rounded to the compute
    dtype once, here, instead of inside every call.

    The step's arithmetic is unchanged: it rounds these leaves to the
    compute dtype before any use, and rounding once gives the same bits.
    The decision is per leaf, from its key and its dtype: an array under
    one of ``_STEP_CAST_KEYS`` and wider than the compute dtype is cast (a
    widening cast would hold more bytes than the tree it came from); every
    other leaf (one already in the compute dtype, one read at another
    precision, a quantized ``QTensor``) is returned as the same object, so
    a tree stored in the compute dtype comes back without a copy.
    ``params`` is not mutated: dicts and lists are rebuilt around the
    leaves.

    The audit (every read of each key in ``models/`` and ``serve/``):

    * cast, read only as ``.astype(cdt)``:
      attention ``wq wk wv wo bq bk bv`` (``transformer._qkv``, the
      ``attn_*`` functions and ``prefill_suffix`` for ``wo``, ``encdec``);
      FFN ``w_gate w_up w_down`` (``blocks.mlp``, ``moe._expert_ffn``,
      ``moe_ffn_sharded``, ``moe_ffn_dropless``); short convolution
      ``in_proj out_proj conv_w`` (``shortconv``; ``shortconv_step``
      widens ``conv_w`` to float32 after the cast); Mamba ``in_proj
      out_proj conv_w conv_b x_proj dt_proj dt_bias D`` (``mamba_forward``,
      ``mamba_decode_step``, ``transformer._mamba_forward_with_state``).
    * kept, read at another precision: ``embed`` and ``unembed`` (the head
      reads them in float32, ``transformer.unembed``), every norm weight
      (``blocks.rmsnorm`` / ``layernorm`` read float32), ``router`` and
      the expert ``bias`` (``moe.route``, float32), ``A_log`` (float32).
      The PTQ tree ``qmlp`` (int8 / w4a8) holds no float leaf.
    """
    cdt = jnp.dtype(cfg.compute_dtype)

    def walk(node, key):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v, key) for v in node]
        if (key in _STEP_CAST_KEYS and isinstance(node, jax.Array)
                and node.dtype.itemsize > cdt.itemsize):
            return node.astype(cdt)
        return node
    return walk(params, None)


def cache_specs(cfg: ModelConfig):
    if cfg.family == "encdec":
        return E.encdec_cache_specs()
    return T.cache_specs(cfg)


# ------------------------------------------------------- KV-slot surgery --
#
# The continuous-batching engine (repro.serve) keeps ONE live batched decode
# cache with per-slot sequence lengths, and splices freshly prefilled
# requests into free slots between decode rounds. These helpers own the
# cache-layout knowledge so the engine stays family-agnostic.


def slot_batch_axes(cfg: ModelConfig) -> dict:
    """Batch axis of every slotted cache leaf (``"len"`` excluded).

    Dense/moe/vlm caches are {k, v} with layout (L, B, S, Hkv, Dh); ssm
    recurrent state is (L, B, ...); hybrid stacks mamba state per super-block
    as (nb, nm, B, ...). encdec's cross-attention cache is not slotted.
    """
    if cfg.family == "encdec":
        raise NotImplementedError(
            "slot surgery: encdec cross-attention caches are per-batch, "
            "not per-slot; serve encdec through the static scheduler")
    if cfg.family == "ssm":
        return {"conv": 1, "ssm": 1}
    if cfg.family == "hybrid":
        return {"k": 1, "v": 1, "conv": 2, "ssm": 2}
    return {"k": 1, "v": 1}


def slot_state_axes(cfg: ModelConfig) -> dict:
    """Batch axis of every per-slot state leaf a paged cache holds beside
    its KV pages: a pattern stack's short-convolution state ``conv``
    (L_conv, B, K - 1, d). Written at admission (:func:`write_slot_state`),
    carried by decode, cleared at retirement (:func:`cache_free_slot`)."""
    return {"conv": 1} if cfg.layer_types and cfg.n_conv_layers else {}


def init_slot_cache(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16, kv: str = "float"):
    """A batched decode cache with per-slot lengths.

    Identical to ``transformer.init_cache`` except ``"len"`` is a (batch,)
    int32 vector — one logical sequence length per slot. Every slot starts
    empty: length 0 masks the entire row out of attention, so uninitialized
    K/V never pollutes a live sequence.

    ``kv="int8"`` stores K/V as int8 codes plus per-(position, head) f32
    scales (``k_scale``/``v_scale``, (L, B, S, Hkv)) — ~halved cache bytes.
    ``cache_write_slot`` quantizes prefilled float K/V on the way in and
    ``decode_step`` quantizes each new token's K/V at its own position
    (per-token scales: refill/retire never re-scales a neighbour).
    Attention-family dense caches only.
    """
    if kv not in ("float", "int8"):
        raise ValueError(f"init_slot_cache: kv must be 'float' or 'int8', "
                         f"got {kv!r}")
    if kv == "int8" and cfg.family in ("ssm", "hybrid", "encdec"):
        raise NotImplementedError(
            "int8 KV slot cache only covers attention-family dense caches")
    cache = T.init_cache(cfg, batch, max_len, dtype)
    if kv == "int8":
        sc = cache["k"].shape[:-1]          # (L, B, S, Hkv)
        cache["k"] = jnp.zeros(cache["k"].shape, jnp.int8)
        cache["v"] = jnp.zeros(cache["v"].shape, jnp.int8)
        cache["k_scale"] = jnp.ones(sc, jnp.float32)
        cache["v_scale"] = jnp.ones(sc, jnp.float32)
    cache["len"] = jnp.zeros((batch,), jnp.int32)
    return cache


def cache_write_slot(cfg: ModelConfig, live: dict, new: dict, slot,
                     src: int = 0) -> dict:
    """Write row ``src`` of a freshly prefilled cache into slot ``slot`` of a
    live batched cache: K/V (and recurrent state) plus the slot's position.

    ``slot`` may be a traced scalar, so a single jit of this function covers
    every slot index. ``new["len"]`` may be the scalar a plain prefill
    produces or the (B,) vector of a ``prompt_lens`` prefill.

    When ``live`` is an int8 KV cache (has ``k_scale``), the prefilled
    *float* K/V row is quantized on the way in — prefill always runs float;
    only the resident cache is int8.
    """
    out = dict(live)
    kv8 = "k_scale" in live
    for key, ax in slot_batch_axes(cfg).items():
        row = jnp.take(new[key], src, axis=ax)
        if kv8 and key in ("k", "v"):
            qrow, srow = A.quantize_kv(row)          # (L,S,Hkv,D) -> (L,S,Hkv)
            out[key] = live[key].at[:, slot].set(qrow)
            out[key + "_scale"] = live[key + "_scale"].at[:, slot].set(srow)
            continue
        row = row.astype(live[key].dtype)
        if ax == 1:
            out[key] = live[key].at[:, slot].set(row)
        else:
            out[key] = live[key].at[:, :, slot].set(row)
    nl = jnp.asarray(new["len"])
    if nl.ndim:
        nl = nl[src]
    out["len"] = live["len"].at[slot].set(nl.astype(jnp.int32))
    return out


def write_slot_state(cfg: ModelConfig, live: dict, new: dict, slot,
                     src: int = 0) -> dict:
    """Write row ``src`` of a fresh prefill's per-slot state leaves
    (:func:`slot_state_axes`) into slot ``slot`` of a paged cache, whose
    KV goes to pages (:func:`paged_write_prompt`)."""
    out = dict(live)
    for key, ax in slot_state_axes(cfg).items():
        row = jnp.take(new[key], src, axis=ax).astype(live[key].dtype)
        out[key] = live[key].at[:, slot].set(row)
    return out


def cache_free_slot(live: dict, slot) -> dict:
    """Retire a slot by zeroing its length — the per-slot attention mask
    makes the stale K/V unreachable, so no data movement is needed. A
    paged cache's short-convolution state (``conv``) is cleared too: the
    next admission writes its own, and a stale one never reaches another
    request."""
    out = dict(live, len=live["len"].at[slot].set(0))
    if "conv" in live and "block_table" in live:
        out["conv"] = live["conv"].at[:, slot].set(0)
    return out


# ----------------------------------------------------------- paged KV pool --
#
# The paged layout replaces per-slot (max_len,) KV rows with a shared pool
# of fixed-size pages: pools (L, num_blocks, block_size, Hkv, Dh) per K and
# V, plus one (B, max_len // block_size) int32 block table shared by every
# layer. Page 0 is RESERVED as the garbage page: it is never allocated, so
# a retired slot's zeroed table row scatters its (masked, never-read)
# decode writes there without touching any live page. The engine owns
# allocation host-side (serve.BlockPool) and re-uploads the table between
# decode rounds, exactly like the host-side ``len`` vector.


def init_paged_cache(cfg: ModelConfig, batch: int, num_blocks: int,
                     block_size: int, max_len: int, dtype=jnp.bfloat16,
                     kv: str = "float"):
    """A paged decode cache: K/V pools + per-slot block tables + lengths.

    Float pools are (L, NB, bs, Hkv * D): one page of one layer is one
    contiguous (bs, Hkv * D) tile, which the paged attention kernel reads
    where it lies (``repro.kernels.paged_attention``). ``kv="int8"``
    stores pool pages as int8 codes plus per-(position, head)
    f32 scale pools (``k_scale``/``v_scale``, (L, NB, bs, Hkv)) — the same
    per-token quantization as the contiguous int8 slot cache, so gathered
    pages dequantize bit-identically. A pattern stack (LFM2) pools its
    attention layers only, in their order, and adds its per-slot state
    (:func:`slot_state_axes`) and ``moe_counts``; its pools are float.
    Attention-family dense caches only;
    ``block_size`` must divide ``max_len`` (the gathered view then has
    length exactly ``max_len``, which is what makes paged decode attention
    bit-identical to the contiguous path — see attention.gather_kv_blocks).
    """
    if kv not in ("float", "int8"):
        raise ValueError(f"init_paged_cache: kv must be 'float' or 'int8', "
                         f"got {kv!r}")
    if cfg.family in ("ssm", "hybrid", "encdec"):
        raise NotImplementedError(
            "paged KV cache only covers attention-family dense caches")
    if max_len % block_size:
        raise ValueError(f"init_paged_cache: block_size={block_size} must "
                         f"divide max_len={max_len}")
    if num_blocks < 2:
        raise ValueError("init_paged_cache: need >= 2 blocks (page 0 is the "
                         "reserved garbage page)")
    L, hkv, dh = cfg.n_attn_layers, cfg.n_kv_heads, cfg.head_dim
    shape = (L, num_blocks, block_size, hkv, dh)
    if kv == "int8":
        cache = {"k": jnp.zeros(shape, jnp.int8),
                 "v": jnp.zeros(shape, jnp.int8),
                 "k_scale": jnp.ones(shape[:-1], jnp.float32),
                 "v_scale": jnp.ones(shape[:-1], jnp.float32)}
    else:
        flat = (L, num_blocks, block_size, hkv * dh)
        cache = {"k": jnp.zeros(flat, dtype), "v": jnp.zeros(flat, dtype)}
    if cfg.layer_types:
        cache.update(T.init_pattern_state(cfg, batch, dtype))
    cache["block_table"] = jnp.zeros((batch, max_len // block_size),
                                     jnp.int32)
    cache["len"] = jnp.zeros((batch,), jnp.int32)
    return cache


def _scatter_pages(live: dict, key: str, seg, ids):
    """Write a (L, n*bs, ...) contiguous segment into pages ``ids`` of pool
    leaf ``key`` (quantizing on the way in when the pool is int8)."""
    out = {}
    n, bs = len(ids), live["k"].shape[2]
    if "k_scale" in live and key in ("k", "v"):
        qseg, sseg = A.quantize_kv(seg)           # (L,n*bs,Hkv,D)->(L,n*bs,Hkv)
        L = qseg.shape[0]
        out[key] = live[key].at[:, ids].set(
            qseg.reshape((L, n, bs) + qseg.shape[2:]))
        out[key + "_scale"] = live[key + "_scale"].at[:, ids].set(
            sseg.reshape((L, n, bs) + sseg.shape[2:]))
    else:
        seg = seg.astype(live[key].dtype)
        L = seg.shape[0]
        out[key] = live[key].at[:, ids].set(
            seg.reshape((L, n, bs) + live[key].shape[3:]))
    return out


def paged_write_prompt(cfg: ModelConfig, live: dict, new: dict, block_ids,
                       *, src: int = 0, skip_blocks: int = 0) -> dict:
    """Scatter row ``src`` of a freshly prefilled contiguous cache into pool
    pages ``block_ids`` of a paged cache.

    ``block_ids`` are the pages for prompt blocks ``skip_blocks ..
    skip_blocks + len(block_ids) - 1`` — a prefix-cache hit passes
    ``skip_blocks > 0`` to leave the shared (already-populated) leading
    pages untouched. Per-position quantization (int8 pools) makes the
    written codes/scales bit-identical to what ``cache_write_slot`` would
    have produced for the same positions, so paged and contiguous decode
    read the very same numbers. The caller updates the block table and
    ``len`` host-side (serve.BlockPool owns both).
    """
    if not len(block_ids):
        return dict(live)
    bs = live["k"].shape[2]
    ids = jnp.asarray(block_ids, jnp.int32)
    lo = skip_blocks * bs
    out = dict(live)
    for key in ("k", "v"):
        row = jnp.take(new[key], src, axis=1)               # (L, S, Hkv, D)
        seg = jax.lax.slice_in_dim(row, lo, lo + len(block_ids) * bs, axis=1)
        out.update(_scatter_pages(live, key, seg, ids))
    return out


def paged_write_kv(live: dict, k_new, v_new, block_ids, *,
                   src: int = 0) -> dict:
    """Scatter freshly computed K/V rows (L, B, S, Hkv, D — e.g. the suffix
    K/V out of ``prefill_suffix_fn``) into pool pages ``block_ids``,
    padding/truncating the sequence to the page span. Positions past the
    real length carry pad K/V exactly as the contiguous cache does —
    masked, never read."""
    if not len(block_ids):
        return dict(live)
    bs = live["k"].shape[2]
    ids = jnp.asarray(block_ids, jnp.int32)
    span = len(block_ids) * bs
    out = dict(live)
    for key, new in (("k", k_new), ("v", v_new)):
        row = jnp.take(new, src, axis=1)                    # (L, S, Hkv, D)
        s = row.shape[1]
        if s < span:
            row = jnp.pad(row, ((0, 0), (0, span - s), (0, 0), (0, 0)))
        elif s > span:
            row = jax.lax.slice_in_dim(row, 0, span, axis=1)
        out.update(_scatter_pages(live, key, row, ids))
    return out


def paged_gather_prefix(live: dict, block_ids):
    """Gather pages ``block_ids`` into contiguous (L, 1, n*bs, Hkv * D)
    prefix K/V for ``prefill_suffix_fn``. Float pools only: a dequantized
    int8 prefix is not the float prefix the donor computed, so int8 prefix
    hits recompute (storage-only sharing) instead of chaining."""
    if "k_scale" in live:
        raise NotImplementedError(
            "paged_gather_prefix: int8 pools share storage only — recompute "
            "the prompt and skip the shared-page writes")
    ids = jnp.asarray(block_ids, jnp.int32)
    outs = []
    for key in ("k", "v"):
        g = live[key][:, ids]                           # (L, n, bs, Hkv*D)
        L = g.shape[0]
        outs.append(g.reshape((L, 1, g.shape[1] * g.shape[2]) + g.shape[3:]))
    return tuple(outs)


# ------------------------------------------------------------ input specs --

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStructs for the cell's step function inputs."""
    b, s = shape.global_batch, shape.seq_len
    cdt = jnp.dtype(cfg.compute_dtype)
    if shape.kind == "train":
        if cfg.family == "vlm":
            p = cfg.frontend_positions
            return {"tokens": _sds((b, s - p), jnp.int32),
                    "embeds": _sds((b, p, cfg.d_model), cdt)}
        if cfg.family == "encdec":
            # split budget: encoder frames S, decoder tokens S (paper-style AST)
            return {"frames": _sds((b, s, cfg.d_model), cdt),
                    "tokens": _sds((b, s), jnp.int32)}
        return {"tokens": _sds((b, s), jnp.int32)}
    if shape.kind == "prefill":
        if cfg.family == "vlm":
            p = cfg.frontend_positions
            return {"tokens": _sds((b, s - p), jnp.int32),
                    "embeds": _sds((b, p, cfg.d_model), cdt)}
        if cfg.family == "encdec":
            return {"frames": _sds((b, s, cfg.d_model), cdt),
                    "tokens": _sds((b, 128), jnp.int32)}
        return {"tokens": _sds((b, s), jnp.int32)}
    # decode: one new token against a cache of seq_len
    specs = {"token": _sds((b, 1), jnp.int32)}
    specs["cache"] = cache_structs(cfg, b, s)
    return specs


def cache_structs(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    # eval_shape: never allocates (decode caches reach tens of GiB)
    if cfg.family != "encdec":
        return jax.eval_shape(
            lambda: T.init_cache(cfg, batch, max_len, dtype))
    return jax.eval_shape(lambda: _encdec_cache_struct(cfg, batch, max_len))


def _encdec_cache_struct(cfg: ModelConfig, batch: int, max_len: int):
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    L = cfg.n_layers
    z = jnp.zeros
    return {"k": z((L, batch, max_len, hkv, dh), jnp.bfloat16),
            "v": z((L, batch, max_len, hkv, dh), jnp.bfloat16),
            "xk": z((L, batch, max_len, hkv, dh), jnp.bfloat16),
            "xv": z((L, batch, max_len, hkv, dh), jnp.bfloat16),
            "len": z((), jnp.int32)}


def param_structs(cfg: ModelConfig):
    """ShapeDtypeStructs of the parameter tree (eval_shape; no allocation)."""
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
