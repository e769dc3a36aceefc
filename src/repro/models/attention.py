"""GQA attention: full (training), flash-scan (prefill), sharded-KV decode (SP).

Memory posture per shape cell (DESIGN.md §5):
  * train_4k   -> ``full`` einsum attention inside a remat'd layer; S=4k
                  scores fit VMEM/HBM budgets and stay differentiable.
  * prefill_32k-> ``flash``: lax.scan over KV blocks with online softmax;
                  O(S·block) memory, no S×S materialization. Inference-only,
                  so no custom VJP is needed.
  * decode_*   -> one-token attention against the KV cache; with SP the
                  cache's seq dim is sharded over "data" and partial
                  (m, l, o) statistics are combined with psum/pmax — the
                  collective payload is O(heads·d) not O(S).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _gqa_scores(q, k):
    """q: (B,Sq,Hkv,G,D); k: (B,Sk,Hkv,D) -> (B,Hkv,G,Sq,Sk) f32."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                      preferred_element_type=jnp.float32)


def _split_gqa(q, n_kv):
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def full_attention(q, k, v, *, causal: bool, q_offset=0):
    """Einsum attention. q:(B,Sq,Hq,D), k/v:(B,Sk,Hkv,D) -> (B,Sq,Hq,D)."""
    b, sq, hq, d = q.shape
    n_kv = k.shape[2]
    qg = _split_gqa(q, n_kv) * (d ** -0.5)
    s = _gqa_scores(qg, k)
    if causal:
        qpos = q_offset + jnp.arange(sq)
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return o.reshape(b, sq, hq, d)


def _flash_fwd_scan(qg, k, v, *, causal, bk, q_offset):
    b, sq = qg.shape[0], qg.shape[1]
    n_kv, g, d = qg.shape[2], qg.shape[3], qg.shape[-1]
    sk = k.shape[1]
    nb = sk // bk
    qpos = q_offset + jnp.arange(sq)
    kb = jnp.moveaxis(k.reshape(b, nb, bk, n_kv, d), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nb, bk, n_kv, d), 1, 0)

    def step(carry, blk):
        m, l, acc = carry
        kc, vc, kk = blk
        s = _gqa_scores(qg, kc)                       # (B,Hkv,G,Sq,bk) f32
        if causal:
            kpos = kk * bk + jnp.arange(bk)
            s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None, None],
                          s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(kc.dtype), vc).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, n_kv, g, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, n_kv, g, sq), jnp.float32),
            jnp.zeros((b, n_kv, g, sq, d), jnp.float32))
    (m, l, acc), _ = lax.scan(step, init, (kb, vb, jnp.arange(nb)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))          # (B,Hkv,G,Sq)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, bk, q_offset):
    qg = _split_gqa(q, k.shape[2]) * (q.shape[-1] ** -0.5)
    o, _ = _flash_fwd_scan(qg, k, v, causal=causal, bk=bk, q_offset=q_offset)
    b, sq, hq, d = q.shape
    return jnp.moveaxis(o, 3, 1).reshape(b, sq, hq, d).astype(q.dtype)


def _flash_vjp_fwd(q, k, v, causal, bk, q_offset):
    qg = _split_gqa(q, k.shape[2]) * (q.shape[-1] ** -0.5)
    o, lse = _flash_fwd_scan(qg, k, v, causal=causal, bk=bk, q_offset=q_offset)
    b, sq, hq, d = q.shape
    out = jnp.moveaxis(o, 3, 1).reshape(b, sq, hq, d).astype(q.dtype)
    return out, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, bk, q_offset, res, do):
    """FlashAttention-style backward: re-scan KV blocks, recompute p from
    the saved logsumexp; O(Sq*bk) transient memory, no S^2 residuals."""
    q, k, v, o, lse = res
    b, sq, hq, d = q.shape
    n_kv = k.shape[2]
    g = hq // n_kv
    sk = k.shape[1]
    nb = sk // bk
    scale = d ** -0.5
    qg = (_split_gqa(q, n_kv) * scale).astype(jnp.float32)
    qg = jnp.moveaxis(qg, 1, 3)                        # (B,Hkv,G,Sq,D)
    dog = jnp.moveaxis(_split_gqa(do, n_kv), 1, 3).astype(jnp.float32)
    delta = jnp.sum(dog * o, axis=-1)                  # (B,Hkv,G,Sq)
    qpos = q_offset + jnp.arange(sq)
    kb = jnp.moveaxis(k.reshape(b, nb, bk, n_kv, d), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nb, bk, n_kv, d), 1, 0)

    def step(dq, blk):
        kc, vc, kk = blk
        s = jnp.einsum("bhgqd,bkhd->bhgqk", qg, kc.astype(jnp.float32))
        if causal:
            kpos = kk * bk + jnp.arange(bk)
            s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None, None],
                          s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                # (B,Hkv,G,Sq,bk)
        dv = jnp.einsum("bhgqk,bhgqd->bkhd", p, dog).astype(v.dtype)
        dp = jnp.einsum("bhgqd,bkhd->bhgqk", dog, vc.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        dq_new = dq + jnp.einsum("bhgqk,bkhd->bhgqd", ds,
                                 kc.astype(jnp.float32)) * scale
        dk = jnp.einsum("bhgqk,bhgqd->bkhd", ds, qg).astype(k.dtype)
        return dq_new, (dk, dv)

    dq0 = jnp.zeros((b, n_kv, g, sq, d), jnp.float32)
    dq, (dks, dvs) = lax.scan(step, dq0, (kb, vb, jnp.arange(nb)))
    dq = jnp.moveaxis(dq, 3, 1).reshape(b, sq, hq, d).astype(q.dtype)
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, sk, n_kv, d)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, sk, n_kv, d)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool, block_k: int = 256,
                    q_offset=0):
    """Blockwise online-softmax attention (differentiable, custom VJP)."""
    sk = k.shape[1]
    bk = min(block_k, sk)
    while sk % bk:
        bk -= 1
    return _flash(q, k, v, causal, bk, q_offset)


def flash_attention_tri(q, k, v, *, block_k: int = 256, n_chunks: int = 8):
    """Causal flash with a static TRIANGLE schedule: q is split into
    n_chunks python-unrolled chunks; chunk i only visits KV blocks
    [0, (i+1)*Sq/n_chunks) — the fully-masked upper-rectangle work of the
    plain scan (≈2x FLOPs at long S) is never issued. §Perf lever."""
    b, sq, hq, d = q.shape
    nc = n_chunks
    while sq % nc:
        nc -= 1
    cq = sq // nc
    outs = []
    for i in range(nc):
        qc = q[:, i * cq:(i + 1) * cq]
        kv_end = (i + 1) * cq
        outs.append(flash_attention(qc, k[:, :kv_end], v[:, :kv_end],
                                    causal=True, block_k=min(block_k, kv_end),
                                    q_offset=i * cq))
    return jnp.concatenate(outs, axis=1)


def attention(q, k, v, *, causal: bool, impl: str = "full", q_offset=0,
              block_k: int = 256):
    if impl == "flash_tri" and causal and q.shape[1] == k.shape[1]:
        return flash_attention_tri(q, k, v, block_k=block_k)
    if impl in ("flash", "flash_tri"):
        return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               block_k=block_k)
    return full_attention(q, k, v, causal=causal, q_offset=q_offset)


# ----------------------------------------------------- int8 KV cache ------
#
# Serving-time KV quantization (same Eq.-4 family as the paper's activation
# quantization, but with a per-token-per-head float scale instead of a
# global pow2 one): each cache position stores int8 codes plus one f32
# scale per (position, kv-head) — a 127-max symmetric quantizer over the
# head_dim vector. Storage is ~halved vs bf16 (1 byte/elem + scale/D), and
# the quantize-on-write / dequantize-on-read pair keeps the attention
# arithmetic itself unchanged. Per-token scales mean a slot refill or
# retirement never re-scales neighbouring positions — exactly the property
# continuous batching needs.

def quantize_kv(x):
    """x: (..., H, D) -> (int8 codes, f32 scales (..., H)).

    Symmetric per-(position, head) quantization: scale = amax/127 over the
    head_dim vector (1.0 for all-zero vectors so the codes stay zero)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q, scale, dtype):
    """Inverse of :func:`quantize_kv` (up to the rounding step)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def decode_attention_q8(q, k_cache, v_cache, k_scale, v_scale, cache_len):
    """:func:`decode_attention` over an int8 KV cache: caches are int8
    (B,S,Hkv,D) + per-(position, head) f32 scales (B,S,Hkv); K/V are
    dequantized on read so masking/softmax numerics match the float path
    on the same codes."""
    k = dequantize_kv(k_cache, k_scale, q.dtype)
    v = dequantize_kv(v_cache, v_scale, q.dtype)
    return decode_attention(q, k, v, cache_len)


# ----------------------------------------------------- paged KV cache -----
#
# Block-pool layout (repro.serve kv_layout="paged"): K/V live in a pool of
# fixed-size pages, (num_blocks, block_size, Hkv, D) per layer, and each
# decode slot owns an ordered block table (max_len // block_size int32 ids)
# instead of a contiguous (S, Hkv, D) row. Gathering the pool rows by table
# reconstructs EXACTLY the contiguous cache a slot would have owned (same
# values at the same positions; table entries past the allocated span point
# at the reserved garbage block 0, whose positions are >= cache_len and
# therefore masked to an exact-zero softmax weight) — so the paged decode
# variants below are bit-identical to their contiguous counterparts by
# construction, provided block_size divides max_len. The int8 pools decode
# through them; float pools decode through the paged attention kernel
# (repro.kernels.paged_attention), which reads only the pages below each
# row's length and is tested against decode_attention_paged.

def gather_kv_blocks(pool, block_table):
    """pool: (NB, bs, ...); block_table: (B, nb) int32 -> (B, nb*bs, ...).

    Row i of the result is slot i's logical cache: block_table[i, j] names
    the pool page holding positions [j*bs, (j+1)*bs)."""
    g = pool[block_table]                          # (B, nb, bs, ...)
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def decode_attention_paged(q, k_pool, v_pool, block_table, cache_len):
    """:func:`decode_attention` over a paged pool: per-layer pools are
    (NB, bs, Hkv, D); the gather-by-block-table view is bit-identical to the
    contiguous cache, so so is the attention output. The reference the
    paged attention kernel is tested against."""
    k = gather_kv_blocks(k_pool, block_table)
    v = gather_kv_blocks(v_pool, block_table)
    return decode_attention(q, k, v, cache_len)


def decode_attention_paged_q8(q, k_pool, v_pool, k_scale_pool, v_scale_pool,
                              block_table, cache_len):
    """:func:`decode_attention_q8` over an int8 paged pool: code pools are
    int8 (NB, bs, Hkv, D) with per-(position, head) f32 scale pools
    (NB, bs, Hkv); dequantize-on-read after the block-table gather."""
    k = gather_kv_blocks(k_pool, block_table)
    v = gather_kv_blocks(v_pool, block_table)
    ks = gather_kv_blocks(k_scale_pool, block_table)
    vs = gather_kv_blocks(v_scale_pool, block_table)
    return decode_attention_q8(q, k, v, ks, vs, cache_len)


# ------------------------------------------------------------- decoding ---

def decode_attention(q, k_cache, v_cache, cache_len):
    """One-token attention over a (possibly longer-than-filled) cache.

    q: (B,1,Hq,D); caches: (B,S,Hkv,D); cache_len: () int32, or (B,) int32
    for per-slot lengths (continuous batching) — row i masks positions
    >= cache_len[i], so stale K/V in retired/padded slots never scores.
    A slot with length 0 attends to nothing (uniform softmax over NEG_INF
    scores); its output is garbage but confined to its own row.
    """
    b, _, hq, d = q.shape
    n_kv = k_cache.shape[2]
    s = k_cache.shape[1]
    qg = _split_gqa(q, n_kv) * (d ** -0.5)
    sc = _gqa_scores(qg, k_cache)                       # (B,Hkv,G,1,S)
    cl = jnp.asarray(cache_len)
    if cl.ndim == 0:
        mask = (jnp.arange(s) < cl)[None, None, None, None]
    else:
        mask = (jnp.arange(s)[None, :] < cl[:, None])[:, None, None, None, :]
    sc = jnp.where(mask, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache)
    return o.reshape(b, 1, hq, d)


def decode_attention_partial(q, k_shard, v_shard, valid_mask):
    """Per-shard flash statistics for SP decode: returns (m, l, o_unnorm)."""
    n_kv = k_shard.shape[2]
    d = q.shape[-1]
    qg = _split_gqa(q, n_kv) * (d ** -0.5)
    sc = _gqa_scores(qg, k_shard)                       # (B,Hkv,G,1,Sloc)
    sc = jnp.where(valid_mask[None, None, None, None], sc, NEG_INF)
    m = jnp.max(sc, axis=-1)
    p = jnp.exp(sc - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(q.dtype), v_shard)
    return m, l, o.astype(jnp.float32)


def sp_combine(m, l, o, axis_name: str):
    """Combine per-shard (m, l, o·l-weighted) stats across the SP axis.

    Collective payload: 2 scalars + d floats per (head, query) — O(S/shards)
    compute, O(d) comms. This is the decode-side analogue of flash's online
    softmax, distributed over the mesh.
    """
    m_glob = lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_glob)
    l_glob = lax.psum(l * corr, axis_name)
    o_glob = lax.psum(o * corr[..., None], axis_name)
    return o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
