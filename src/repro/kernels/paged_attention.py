"""Paged decode attention: one query token per slot against the KV pages
its block table names, read where they lie in the stacked pool.

The serving engine's paged float cache keeps K and V as stacked pools
``(L, NB, bs, Hkv * D)``, so one page of one layer is one ``(bs, Hkv * D)``
tile, contiguous in HBM: for qwen2-0.5b a bf16 ``(16, 128)`` tile. (With
the heads unflattened, ``(..., bs, Hkv, D)``, the TPU lays the pool out
with the page index minor, and every page is strided over the whole
pool.) The kernel takes the whole stacked
pool in HBM, the layer index, the block table and each row's length as
scalar prefetch, and per grid step (one slot) walks only the pages below
that slot's length, copying ``pages_per_chunk`` pages at a time into a
double-buffered VMEM scratch. The copy of the next chunk, or of the next
slot's first chunk, is in flight while the current one is scored, so grid
steps scale with slots and not with ``max_len``. No page past a row's
length is copied.

GQA without lane slicing: the wrapper lays each query head into a
block-diagonal row of width ``Hkv * D`` (its own kv head's lanes hold the
query, the others zero), so one ``(rows, Hkv*D) x (tokens, Hkv*D)^T``
contraction scores every head against its own kv head, and the output row
keeps only its own head's lanes. Softmax statistics and the output
accumulate in float32 (online softmax); probabilities enter the value
contraction in the pool's dtype, as the gather path's do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import SUBLANE, resolve_interpret, round_up

NEG_INF = -1e30


def _kernel(layer_ref, len_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, state, *, bs: int, pages: int, nb: int,
            nslots: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    span = pages * bs
    rows, width = q_ref.shape[1], q_ref.shape[2]

    def n_chunks(i):
        return (len_ref[i] + span - 1) // span

    def each_page(i, c, fn):
        """``fn(j)`` for page j of chunk c of row i, for the pages the row
        holds. A loop, not an unrolled Python one: unrolled, the kernel
        took seconds to trace at 32 pages a chunk."""
        def body(j, carry):
            fn(j)
            return carry
        held = (len_ref[i] + bs - 1) // bs - c * pages
        lax.fori_loop(0, jnp.clip(held, 0, pages), body, 0)

    def start(i, c, slot):
        def go(j):
            page = table_ref[i * nb + c * pages + j]
            pltpu.make_async_copy(k_hbm.at[layer, page], kbuf.at[slot, j],
                                  sems.at[0, slot]).start()
            pltpu.make_async_copy(v_hbm.at[layer, page], vbuf.at[slot, j],
                                  sems.at[1, slot]).start()
        each_page(i, c, go)

    def wait(i, c, slot):
        def go(j):
            pltpu.make_async_copy(k_hbm.at[layer, 0], kbuf.at[slot, j],
                                  sems.at[0, slot]).wait()
            pltpu.make_async_copy(v_hbm.at[layer, 0], vbuf.at[slot, j],
                                  sems.at[1, slot]).wait()
        each_page(i, c, go)

    # state[0]: the buffer the next chunk lands in; state[1]: 1 when the
    # previous grid step already started this slot's first chunk
    @pl.when(b == 0)
    def _():
        state[0] = 0
        state[1] = 0

    length = len_ref[b]
    chunks = n_chunks(b)

    @pl.when(chunks == 0)
    def _():
        o_ref[0] = jnp.zeros((rows, width), o_ref.dtype)

    @pl.when(chunks > 0)
    def _():
        @pl.when(state[1] == 0)
        def _():
            start(b, 0, state[0])
        state[1] = 0
        q = q_ref[0]

        def body(c, carry):
            m, l, acc = carry
            slot = state[0]
            nxt = 1 - slot

            @pl.when(c + 1 < chunks)
            def _():
                start(b, c + 1, nxt)

            @pl.when(jnp.logical_and(c + 1 == chunks, b + 1 < nslots))
            def _():
                following = jnp.minimum(b + 1, nslots - 1)
                start(following, 0, nxt)
                state[1] = (n_chunks(following) > 0).astype(jnp.int32)

            wait(b, c, slot)
            k = kbuf[slot].reshape(span, width)
            v = vbuf[slot].reshape(span, width)
            base = c * span
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            valid = base + lax.broadcasted_iota(jnp.int32, s.shape, 1) < length
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l_new = corr * l + jnp.sum(p, axis=1, keepdims=True)
            # rows of v past the length were never copied: zero them so
            # stale scratch cannot reach the sum, even as NaN * 0
            v_ok = base + lax.broadcasted_iota(jnp.int32, (span, 1), 0) < length
            v = jnp.where(v_ok, v, jnp.zeros_like(v))
            acc_new = corr * acc + jnp.dot(p.astype(v.dtype), v,
                                           preferred_element_type=jnp.float32)
            state[0] = nxt
            return m_new, l_new, acc_new

        init = (jnp.full((rows, 1), NEG_INF, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, width), jnp.float32))
        _, l, acc = lax.fori_loop(0, chunks, body, init)
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    layer, block_table: jax.Array, lengths: jax.Array, *,
                    pages_per_chunk: int = 32,
                    interpret: bool | None = None) -> jax.Array:
    """One-token GQA attention of each slot over its own pages.

    q: (B, Hq, D); k_pool, v_pool: the stacked pools (L, NB, bs, Hkv * D);
    layer: () int32, the pool layer to read; block_table: (B, nb) int32,
    ``block_table[i, j]`` the page of row i's positions ``[j*bs,
    (j+1)*bs)``; lengths: (B,) int32, the keys row i attends to (its
    positions ``< lengths[i]``). Returns (B, Hq, D) in q's dtype; a row of
    length 0 reads nothing and returns zeros.
    """
    b, hq, d = q.shape
    _, _, bs, width = k_pool.shape
    hkv = width // d
    g = hq // hkv
    nb = block_table.shape[1]
    pages = max(1, min(pages_per_chunk, nb))
    rows = round_up(hq, SUBLANE * 4 // jnp.dtype(k_pool.dtype).itemsize)
    # block-diagonal queries: head (h, g) holds its query in kv head h's
    # lanes and zeros elsewhere (a select, so the values are exact)
    own = jnp.arange(hkv)[:, None] == jnp.arange(hkv)[None, :]
    qs = (q.reshape(b, hkv, g, d) * (d ** -0.5)).astype(k_pool.dtype)
    qbd = jnp.where(own[None, :, None, :, None], qs[:, :, :, None, :], 0)
    qbd = jnp.pad(qbd.reshape(b, hq, width), ((0, 0), (0, rows - hq), (0, 0)))
    kernel = functools.partial(_kernel, bs=bs, pages=pages, nb=nb, nslots=b)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, rows, width),
                                   lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, rows, width), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bs, width), k_pool.dtype),
                pltpu.VMEM((2, pages, bs, width), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, rows, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
        name="paged_attention",
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      lengths.astype(jnp.int32), block_table.astype(jnp.int32).reshape(-1),
      qbd, k_pool, v_pool)
    # each head keeps its own kv head's lanes
    out = out[:, :hq].reshape(b, hkv, g, hkv, d)
    out = jnp.sum(jnp.where(own[None, :, None, :, None], out, 0.0), axis=3)
    return out.reshape(b, hq, d).astype(q.dtype)
