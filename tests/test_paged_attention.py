"""The paged attention kernel (interpret mode on the CPU) against the
gather-by-block-table reference, at the qwen2-0.5b cell's layout (pages of
16 positions, 2 kv heads of 64, 14 query heads), and the paged
``decode_step`` built on it against the contiguous one over several rounds.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import paged_attention as PA
from repro.models import api
from repro.models import attention as A
from repro.models import transformer as T

L, NB, BS, HKV, D, HQ, NBT = 3, 48, 16, 2, 64, 14, 8
# one compile per chunk size: the layer index is traced
paged_attention = jax.jit(PA.paged_attention,
                          static_argnames=("pages_per_chunk",))
BIG = 3.0e4          # finite in bf16; any read of it would swamp the output

# The reference takes a full softmax in f32, normalises it and rounds the
# probabilities to bf16 before the value contraction; the kernel keeps
# running max and sum in f32, rounds the unnormalised probabilities to bf16
# and divides the f32 sum at the end. Both outputs are bf16 of values of
# order 1, so they differ by a few bf16 steps (2**-7 at 1).
ATOL = RTOL = 3e-2


def _pools(seed=0):
    """Stacked pools with normal values on live pages and BIG everywhere
    else (the garbage page 0 and every page no row's length reaches)."""
    rng = np.random.default_rng(seed)
    k = np.full((L, NB, BS, HKV * D), BIG, np.float32)
    v = np.full((L, NB, BS, HKV * D), BIG, np.float32)
    return rng, k, v


def _case(lens, seed=0):
    rng, k, v = _pools(seed)
    lens = np.asarray(lens, np.int32)
    table = np.zeros((len(lens), NBT), np.int32)
    free = list(rng.permutation(np.arange(1, NB)))
    for i, n in enumerate(lens):
        for j in range(-(-int(n) // BS)):
            page = free.pop()
            table[i, j] = page
            live = min(BS, int(n) - j * BS)  # positions past the length: BIG
            k[:, page, :live] = rng.normal(size=(L, live, HKV * D))
            v[:, page, :live] = rng.normal(size=(L, live, HKV * D))
    q = rng.normal(size=(len(lens), HQ, D))
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
            jnp.asarray(v, jnp.bfloat16), jnp.asarray(table),
            jnp.asarray(lens))


def _reference(q, k, v, layer, table, lens):
    per_layer = lambda p: p[layer].reshape(NB, BS, HKV, D)
    return A.decode_attention_paged(q[:, None], per_layer(k), per_layer(v),
                                    table, lens)[:, 0]


@pytest.mark.parametrize("pages_per_chunk", [1, 3, 32])
@pytest.mark.parametrize("lens", [[1], [15], [16], [17], [NBT * BS],
                                  [1, 15, 16, 17, NBT * BS, 40, 77]],
                         ids=["1", "15", "16", "17", "full", "mix"])
def test_kernel_matches_the_gather_reference(lens, pages_per_chunk):
    q, k, v, table, ln = _case(lens)
    for layer in range(L):
        got = paged_attention(q, k, v, layer, table, ln,
                              pages_per_chunk=pages_per_chunk)
        ref = _reference(q, k, v, layer, table, ln)
        assert got.shape == (len(lens), HQ, D) and got.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=ATOL, rtol=RTOL)


def test_rows_of_length_zero_read_nothing():
    """Retired rows (length 0, table row all page 0) give zeros, no NaN,
    and leave their neighbours' results as they were."""
    q, k, v, table, ln = _case([0, 17, 0, 33, 0])
    got = np.asarray(paged_attention(q, k, v, 1, table, ln,
                                     pages_per_chunk=2), np.float32)
    assert np.isfinite(got).all()
    assert not got[[0, 2, 4]].any()
    ref = np.asarray(_reference(q, k, v, 1, table, ln), np.float32)
    np.testing.assert_allclose(got[[1, 3]], ref[[1, 3]], atol=ATOL, rtol=RTOL)


def test_unread_pages_never_reach_the_output():
    """The garbage page and the pages past each length hold BIG: a kernel
    that read one would return values near BIG; outputs stay within the
    range of the live values."""
    q, k, v, table, ln = _case([1, 15, 16, 17, 40])
    got = np.asarray(paged_attention(q, k, v, 2, table, ln), np.float32)
    assert np.abs(got).max() < 10.0


# ---------------------------------------------------------- decode step


def _tiny_cfg():
    return dataclasses.replace(get_config("qwen2-0.5b"), n_layers=2,
                               d_model=64, n_heads=4, n_kv_heads=2, d_ff=64,
                               vocab=64)


def test_paged_decode_step_tracks_the_contiguous_one():
    """Several greedy rounds of the paged ``decode_step`` (the kernel) and
    the contiguous one from the same prefill, fed the same tokens. Logits
    agree within the kernel's tolerance; after each round the pools differ
    from the round before only at ``[l, block, offset]`` of each row's new
    position, where they hold the new K/V: bit for bit what the contiguous
    cache holds in layer 0 (whose K/V no attention has touched yet), and
    within the tolerance in the layers after it."""
    cfg = _tiny_cfg()
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    bs, max_len, nb = 8, 32, 16
    plens = np.array([5, 8, 13], np.int32)
    b = len(plens)
    rng = np.random.default_rng(0)
    toks = np.zeros((b, 16), np.int32)
    for i, p in enumerate(plens):
        toks[i, :p] = rng.integers(0, cfg.vocab, p)
    logits, cont = T.prefill(params, jnp.asarray(toks), cfg, max_len,
                             prompt_lens=jnp.asarray(plens))
    paged = api.init_paged_cache(cfg, b, nb, bs, max_len)
    table = np.zeros((b, max_len // bs), np.int32)
    free = list(rng.permutation(np.arange(1, nb)))
    for i in range(b):
        ids = [free.pop() for _ in range(max_len // bs)]
        table[i] = ids
        n = -(-int(plens[i]) // bs)
        paged = api.paged_write_prompt(cfg, paged, cont, ids[:n], src=i)
    paged["block_table"] = jnp.asarray(table)
    paged["len"] = jnp.asarray(plens)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    step = jax.jit(functools.partial(T.decode_step, cfg=cfg))
    prev = {key: np.asarray(paged[key]) for key in ("k", "v")}
    rows = np.arange(b)
    for _ in range(6):
        lens = np.asarray(cont["len"])
        lg_c, cont = step(params, tok, cont)
        lg_p, paged = step(params, tok, paged)
        # the kernel's bf16 steps, carried through two layers of bf16
        # activations into the logits
        np.testing.assert_allclose(np.asarray(lg_p, np.float32),
                                   np.asarray(lg_c, np.float32),
                                   atol=5e-2, rtol=5e-2)
        assert (np.asarray(paged["len"]) == lens + 1).all()
        blk, off = table[rows, lens // bs], lens % bs
        for key in ("k", "v"):
            pool = np.asarray(paged[key])
            new = pool[:, blk, off]                # (L, B, Hkv*D)
            want = np.asarray(cont[key])[:, rows, lens].reshape(new.shape)
            np.testing.assert_array_equal(new[0], want[0])
            np.testing.assert_allclose(new.astype(np.float32),
                                       want.astype(np.float32),
                                       atol=5e-2, rtol=5e-2)
            before = prev[key].copy()
            before[:, blk, off] = new
            np.testing.assert_array_equal(pool, before)
            prev[key] = pool
        tok = jnp.argmax(lg_c[:, -1], axis=-1).astype(jnp.int32)[:, None]
