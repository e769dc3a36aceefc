"""Paged-KV serving tests: bit-identical greedy streams vs the contiguous
layout (float + int8 KV, mid-decode retire/refill, growth at page
boundaries), hash-based prefix sharing (refcount correctness under
different retirement orders, storage-only int8 sharing), pool-exhaustion
admission backpressure and mid-decode preemption, BlockPool unit behaviour,
the paged config checks, and the suffix-prefill exactness they all rest on.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.serve import BlockPool, Engine, Request, ServeConfig


def tiny_cfg():
    return dataclasses.replace(get_config("qwen2-0.5b"), n_layers=2,
                               d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                               vocab=64)


@pytest.fixture(scope="module")
def dense_setup():
    cfg = tiny_cfg()
    return cfg, api.init_params(cfg, jax.random.PRNGKey(0))


def make_req(uid, plen=5, max_new=6, seed=None, **kw):
    rng = np.random.default_rng(uid if seed is None else seed)
    return Request(uid=uid,
                   prompt=rng.integers(0, 64, (plen,)).astype(np.int32),
                   max_new_tokens=max_new, **kw)


def make_prefixed(uid, shared, suffix_len, max_new=6, **kw):
    rng = np.random.default_rng(1000 + uid)
    sfx = rng.integers(0, 64, (suffix_len,)).astype(np.int32)
    return Request(uid=uid, prompt=np.concatenate([shared, sfx]),
                   max_new_tokens=max_new, **kw)


def drain(cfg, params, reqs, **scfg_kw):
    eng = Engine(cfg, params, ServeConfig(**scfg_kw))
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    return eng, sorted(done, key=lambda r: r.uid)


def streams(done):
    return {r.uid: list(r.out_tokens) for r in done}


# ------------------------------------------------- paged-contiguous parity


def test_paged_parity_mid_decode_refill(dense_setup):
    """Greedy streams are bit-identical to the contiguous layout through
    mid-decode retirements and slot refills (staggered max_new keeps slots
    churning), including prompts that cross page boundaries."""
    cfg, params = dense_setup
    reqs = [make_req(i, plen=p, max_new=m) for i, (p, m) in
            enumerate([(5, 9), (13, 2), (8, 7), (16, 4), (3, 11), (9, 1)])]
    _, ref = drain(cfg, params, [dataclasses.replace(r) for r in reqs],
                   max_batch=2, max_len=32)
    _, got = drain(cfg, params, reqs, max_batch=2, max_len=32,
                   kv_layout="paged", kv_block_size=8)
    assert streams(got) == streams(ref)
    assert all(r.done for r in got)


def test_paged_parity_int8_kv(dense_setup):
    cfg, params = dense_setup
    reqs = [make_req(i, plen=p, max_new=m) for i, (p, m) in
            enumerate([(6, 8), (11, 3), (15, 6), (4, 10)])]
    _, ref = drain(cfg, params, [dataclasses.replace(r) for r in reqs],
                   max_batch=2, max_len=32, kv_cache="int8")
    _, got = drain(cfg, params, reqs, max_batch=2, max_len=32,
                   kv_cache="int8", kv_layout="paged", kv_block_size=8)
    assert streams(got) == streams(ref)


def test_paged_growth_at_page_boundary(dense_setup):
    """A prompt landing exactly on a page boundary needs a fresh page
    before its first decode write; generation then crosses further
    boundaries. Streams must still match contiguous bit-for-bit."""
    cfg, params = dense_setup
    reqs = [make_req(0, plen=8, max_new=20), make_req(1, plen=16, max_new=12)]
    _, ref = drain(cfg, params, [dataclasses.replace(r) for r in reqs],
                   max_batch=2, max_len=64)
    eng, got = drain(cfg, params, reqs, max_batch=2, max_len=64,
                     kv_layout="paged", kv_block_size=8)
    assert streams(got) == streams(ref)
    # all pages returned once the drain retired everything
    assert eng.stats["blocks_in_use"] == 0


def test_paged_single_request_vs_contiguous(dense_setup):
    cfg, params = dense_setup
    _, ref = drain(cfg, params, [make_req(0, plen=7, max_new=12)],
                   max_batch=4, max_len=32)
    _, got = drain(cfg, params, [make_req(0, plen=7, max_new=12)],
                   max_batch=4, max_len=32, kv_layout="paged",
                   kv_block_size=16)
    assert streams(got) == streams(ref)


def test_kv_pages_read_counts_the_pages_rows_hold(dense_setup):
    """``serve.kv_pages_read`` adds, per decode round, the pages each
    active row holds with its new position: a 7-token prompt decoding 11
    tokens at 16-position pages holds one page at lengths 7..15 (9 rounds)
    and two at 16 and 17 (2 rounds); a second row of 20 tokens decoding 2
    holds two pages in both of its rounds. The contiguous layout reads no
    pages."""
    cfg, params = dense_setup
    reqs = [make_req(0, plen=7, max_new=12), make_req(1, plen=20, max_new=3)]
    eng, _ = drain(cfg, params, [dataclasses.replace(r) for r in reqs],
                   max_batch=2, max_len=32, kv_layout="paged",
                   kv_block_size=16)
    assert eng.metrics.counter("serve.kv_pages_read").value == 9 + 4 + 4
    eng, _ = drain(cfg, params, reqs, max_batch=2, max_len=32)
    assert eng.metrics.counter("serve.kv_pages_read").value == 0


# ------------------------------------------------------------ prefix reuse


def test_prefix_sharing_hits_and_parity(dense_setup):
    """Requests sharing a long prompt prefix hit the donor's published
    pages (block-granular hit rate > 0, fewer prefilled positions) and
    still produce streams bit-identical to contiguous serving."""
    cfg, params = dense_setup
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 64, (17,)).astype(np.int32)  # 2 full 8-blocks
    reqs = [make_prefixed(i, shared, s, max_new=m) for i, (s, m) in
            enumerate([(3, 6), (5, 4), (1, 8), (9, 2)])]
    _, ref = drain(cfg, params, [dataclasses.replace(r) for r in reqs],
                   max_batch=2, max_len=64)
    eng, got = drain(cfg, params, reqs, max_batch=2, max_len=64,
                     kv_layout="paged", kv_block_size=8)
    assert streams(got) == streams(ref)
    st = eng.stats
    assert st["prefix_hit_rate"] > 0.0
    # requests 1..3 each hit the donor's two published prefix pages
    assert st["blocks_in_use"] == 0 and st["blocks_free"] > 0


def test_prefix_refcount_survives_retire_orders(dense_setup):
    """Sharers retiring in different orders (staggered max_new both ways)
    must leave the pool fully drained — refcounts hit zero exactly once
    per page, and streams match the contiguous baseline in both orders."""
    cfg, params = dense_setup
    rng = np.random.default_rng(8)
    shared = rng.integers(0, 64, (17,)).astype(np.int32)
    for maxnews in ([2, 9], [9, 2]):        # donor first / donor last
        reqs = [make_prefixed(i, shared, 3 + i, max_new=m)
                for i, m in enumerate(maxnews)]
        _, ref = drain(cfg, params, [dataclasses.replace(r) for r in reqs],
                       max_batch=2, max_len=64)
        eng, got = drain(cfg, params, reqs, max_batch=2, max_len=64,
                         kv_layout="paged", kv_block_size=8)
        assert streams(got) == streams(ref)
        assert eng.stats["blocks_in_use"] == 0


def test_prefix_sharing_int8_storage_only(dense_setup):
    """int8 KV shares page STORAGE (hit rate > 0, shared pages written
    once) but recomputes each hitting prompt — streams still match the
    contiguous int8 baseline bit-for-bit."""
    cfg, params = dense_setup
    rng = np.random.default_rng(9)
    shared = rng.integers(0, 64, (17,)).astype(np.int32)
    reqs = [make_prefixed(i, shared, 2 + i, max_new=5) for i in range(3)]
    _, ref = drain(cfg, params, [dataclasses.replace(r) for r in reqs],
                   max_batch=2, max_len=64, kv_cache="int8")
    eng, got = drain(cfg, params, reqs, max_batch=2, max_len=64,
                     kv_cache="int8", kv_layout="paged", kv_block_size=8)
    assert streams(got) == streams(ref)
    assert eng.stats["prefix_hit_rate"] > 0.0


def test_prefix_cache_disabled(dense_setup):
    cfg, params = dense_setup
    rng = np.random.default_rng(10)
    shared = rng.integers(0, 64, (17,)).astype(np.int32)
    reqs = [make_prefixed(i, shared, 2, max_new=4) for i in range(3)]
    _, ref = drain(cfg, params, [dataclasses.replace(r) for r in reqs],
                   max_batch=2, max_len=64)
    eng, got = drain(cfg, params, reqs, max_batch=2, max_len=64,
                     kv_layout="paged", kv_block_size=8, prefix_cache=False)
    assert streams(got) == streams(ref)
    assert eng.stats["prefix_hit_rate"] == 0.0


# -------------------------------------------- backpressure and preemption


def test_pool_exhaustion_admission_backpressure(dense_setup):
    """A pool too small for max_batch concurrent requests parks admissions
    in the holdback instead of failing; every request still completes with
    the contiguous baseline's exact stream."""
    cfg, params = dense_setup
    reqs = [make_req(i, plen=12, max_new=10) for i in range(5)]
    _, ref = drain(cfg, params, [dataclasses.replace(r) for r in reqs],
                   max_batch=4, max_len=32)
    # 5 usable pages of 8 positions: at most ~2 requests resident at once
    eng, got = drain(cfg, params, reqs, max_batch=4, max_len=32,
                     kv_layout="paged", kv_block_size=8, kv_num_blocks=6)
    assert streams(got) == streams(ref)
    assert eng.stats["requests_done"] == 5
    assert eng.stats["blocks_in_use"] == 0


def test_preemption_replays_identical_stream(dense_setup):
    """The minimum legal pool (one max_len sequence) forces mid-decode
    preemption when a second request is admitted; the preempted request
    replays from its prompt and the final streams still match contiguous."""
    cfg, params = dense_setup
    reqs = [make_req(i, plen=9, max_new=16) for i in range(3)]
    _, ref = drain(cfg, params, [dataclasses.replace(r) for r in reqs],
                   max_batch=2, max_len=32)
    eng, got = drain(cfg, params, reqs, max_batch=2, max_len=32,
                     kv_layout="paged", kv_block_size=8, kv_num_blocks=5)
    assert streams(got) == streams(ref)
    assert all(r.done for r in got)


# ------------------------------------------------------------- rejections


def test_paged_rejects_bad_configs(dense_setup):
    cfg, params = dense_setup
    with pytest.raises(ValueError, match="kv_layout"):
        Engine(cfg, params, ServeConfig(kv_layout="chunked"))
    with pytest.raises(NotImplementedError, match="static"):
        Engine(cfg, params, ServeConfig(kv_layout="paged",
                                        scheduler="static"))
    with pytest.raises(ValueError, match="divide"):
        Engine(cfg, params, ServeConfig(kv_layout="paged", max_len=40,
                                        kv_block_size=16))
    with pytest.raises(ValueError, match="usable"):
        Engine(cfg, params, ServeConfig(kv_layout="paged", max_len=64,
                                        kv_block_size=16, kv_num_blocks=3))


def test_paged_rejects_recurrent_families():
    ssm = dataclasses.replace(get_config("falcon-mamba-7b"), n_layers=2,
                              d_model=32, d_ff=64, vocab=64)
    with pytest.raises(NotImplementedError, match="attention-family"):
        Engine(ssm, api.init_params(ssm, jax.random.PRNGKey(0)),
               ServeConfig(kv_layout="paged"))


def test_unified_prompt_length_message(dense_setup):
    """Submit-time and admit-time oversized-prompt rejections share ONE
    message (they used to diverge)."""
    cfg, params = dense_setup
    eng = Engine(cfg, params, ServeConfig(max_batch=2, max_len=16))
    big = make_req(7, plen=17)
    with pytest.raises(ValueError, match=r"request 7: prompt length 17 "
                                         r"exceeds max_len=16"):
        eng.submit(big)
    # bypass submit: the admit path must reject with the same message
    eng.queue.put(big)
    with pytest.raises(ValueError, match=r"request 7: prompt length 17 "
                                         r"exceeds max_len=16"):
        eng.run_until_drained()


# ---------------------------------------------------------- pool unit-ness


def test_blockpool_alloc_free_lru():
    pool = BlockPool(num_blocks=6, block_size=8)
    assert pool.usable == 5 and pool.free_pages == 5
    a = pool.alloc(3)
    assert a == [1, 2, 3] and pool.in_use == 3
    assert pool.alloc(3) is None            # only 2 left -> backpressure
    pool.free(a)
    assert pool.free_pages == 5 and pool.in_use == 0
    assert pool.alloc(6) is None            # beyond usable, ever


def test_blockpool_prefix_publish_refcount_evict():
    pool = BlockPool(num_blocks=4, block_size=4)
    prompt = np.arange(9, dtype=np.int32)   # 2 full blocks hashable
    keys = pool.prefix_keys(prompt)
    assert len(keys) == 2
    assert pool.lookup(keys) == []
    ids = pool.alloc(2)
    pool.publish(keys, ids)                 # donor's live reference
    assert pool.lookup(keys) == ids
    # a second sharer acquires, both release -> pages park evictable
    pool.acquire(ids)
    pool.release(ids)
    pool.free(ids, hashed=len(ids))         # donor retires
    assert pool.in_use == 0 and pool.free_pages == 3
    assert pool.lookup(keys) == ids         # retained: still hits
    # pressure reclaims LRU evictable pages and drops their digests
    got = pool.alloc(3)
    assert set(ids) <= set(got)
    assert pool.lookup(keys) == []


def test_blockpool_chained_keys_diverge():
    pool = BlockPool(num_blocks=8, block_size=4)
    a = pool.prefix_keys(np.arange(12, dtype=np.int32))
    b = pool.prefix_keys(np.concatenate([np.arange(4, dtype=np.int32),
                                         np.arange(100, 108,
                                                   dtype=np.int32)]))
    assert a[0] == b[0]                     # identical first block
    assert a[1] != b[1]                     # chained: diverges after


def test_blockpool_no_prefix_cache():
    pool = BlockPool(num_blocks=4, block_size=4, prefix_cache=False)
    assert pool.prefix_keys(np.arange(12, dtype=np.int32)) == []


# ------------------------------------------------------- config/budgeting


def test_check_config_paged():
    from repro.check.config import check_serve_config, kv_cache_bytes, \
        paged_num_blocks
    cfg = tiny_cfg()
    ok = ServeConfig(kv_layout="paged", max_len=64, kv_block_size=16)
    assert check_serve_config(ok, cfg) == []
    assert paged_num_blocks(ok) == 4 * 4 + 1
    # paged bytes with default sizing ~= contiguous bytes + garbage page
    # + table overhead
    contig = kv_cache_bytes(cfg, ServeConfig(max_len=64))
    paged = kv_cache_bytes(cfg, ok)
    per_page = cfg.n_layers * 2 * 16 * cfg.n_kv_heads * cfg.head_dim * 2
    assert paged == contig + per_page + 4 * ok.max_batch * 4
    # violations: layout enum, divisibility, deadlock floor, strict
    # max_batch floor
    assert check_serve_config(
        ServeConfig(kv_layout="nope"), cfg)
    assert check_serve_config(
        ServeConfig(kv_layout="paged", max_len=40, kv_block_size=16), cfg)
    assert check_serve_config(
        ServeConfig(kv_layout="paged", max_len=64, kv_block_size=16,
                    kv_num_blocks=4), cfg)
    strict_small = ServeConfig(kv_layout="paged", max_batch=8, max_len=64,
                               kv_block_size=16, kv_num_blocks=5)
    assert check_serve_config(strict_small, cfg, strict=True)
    assert check_serve_config(strict_small, cfg, strict=False) == []


# ------------------------------------------------- suffix-prefill exactness


def test_prefill_suffix_bitwise_exact(dense_setup):
    """The prefix-hit fast path's foundation: running only the suffix
    against the prefix K/V a bucketed prefill produced yields the SAME
    bits as prefilling the whole prompt — logits and suffix K/V alike."""
    cfg, params = dense_setup
    rng = np.random.default_rng(11)
    plen, pfx = 21, 16
    prompt = rng.integers(0, 64, (plen,)).astype(np.int32)
    max_len = 32
    full = jax.jit(api.prefill_fn(cfg, max_len))
    toks = np.zeros((1, max_len), np.int32)
    toks[0, :plen] = prompt
    logits_ref, cache = full(params, {
        "tokens": jnp.asarray(toks),
        "prompt_lens": jnp.asarray([plen], jnp.int32)})
    # donor ran under a DIFFERENT (shorter) bucket: prefix K/V must be
    # bucket-independent for reuse to be legal
    toks_d = np.zeros((1, pfx), np.int32)
    toks_d[0, :] = prompt[:pfx]
    _, donor = jax.jit(api.prefill_fn(cfg, pfx))(
        params, {"tokens": jnp.asarray(toks_d),
                 "prompt_lens": jnp.asarray([pfx], jnp.int32)})
    np.testing.assert_array_equal(np.asarray(cache["k"])[:, :, :pfx],
                                  np.asarray(donor["k"]))
    # suffix-only prefill over the donor's prefix K/V
    sfx = jax.jit(api.prefill_suffix_fn(cfg))
    s_sfx = plen - pfx
    stoks = np.zeros((1, 8), np.int32)      # bucketed past the real suffix
    stoks[0, :s_sfx] = prompt[pfx:]
    logits, ks, vs = sfx(params, {
        "tokens": jnp.asarray(stoks),
        "prefix_k": jnp.asarray(donor["k"]),
        "prefix_v": jnp.asarray(donor["v"]),
        "suffix_lens": jnp.asarray([s_sfx], jnp.int32)})
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits_ref))
    np.testing.assert_array_equal(
        np.asarray(ks)[:, :, :s_sfx],
        np.asarray(cache["k"])[:, :, pfx:pfx + s_sfx])
    np.testing.assert_array_equal(
        np.asarray(vs)[:, :, :s_sfx],
        np.asarray(cache["v"])[:, :, pfx:pfx + s_sfx])
