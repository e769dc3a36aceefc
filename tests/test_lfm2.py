"""LFM2-8B-A1B (a pattern stack: gated short convolutions beside GQA
attention layers, a dropless sigmoid-routed MoE) at tiny widths on the CPU,
against the plain float32 reference kept with the benchmark
(``bench/configs/lfm2-8b-a1b.py``): prefill and paged decode on logits, the
conv state a bucketed prefill leaves, the routing, the ``moe_experts``
kernel, and the engine's paged drain, preemption, counters and refusals.

The comparisons with the reference run the program in float32, so their
tolerances are summation orders: at bfloat16 and these toy widths rounding
flips near-tied expert picks (the chip's check compares the bfloat16
program at the published widths).
"""
import dataclasses
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.models import transformer as T
from repro.models.moe import _capacity, route
from repro.serve import Engine, Request, ServeConfig

REF_PATH = Path(__file__).resolve().parents[1] / "bench" / "configs" / \
    "lfm2-8b-a1b.py"


def _reference():
    spec = importlib.util.spec_from_file_location("lfm2_reference", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_cfg(compute_dtype="bfloat16"):
    """Layers 0-6 of the published pattern: conv, conv, attention, conv,
    conv, conv, attention; 2 dense FFNs, then 5 expert layers."""
    cfg = get_config("lfm2-8b-a1b")
    return dataclasses.replace(
        cfg, n_layers=7, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96, vocab=128, param_dtype="float32",
        compute_dtype=compute_dtype,
        moe=dataclasses.replace(cfg.moe, num_experts=8, top_k=2, d_ff=32))


def ref_conf(cfg):
    """The reference's configuration keys for ``cfg``."""
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "model": {"head_dim": cfg.head_dim,
                      "param_dtype": cfg.param_dtype},
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
            "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "conv_L_cache": cfg.conv_kernel,
            "layer_types": list(cfg.layer_types),
            "num_dense_layers": cfg.n_dense_layers,
            "num_experts": cfg.moe.num_experts,
            "num_experts_per_tok": cfg.moe.top_k,
            "moe_intermediate_size": cfg.moe.d_ff,
            "routed_scaling_factor": 1}


@pytest.fixture(scope="module")
def setup():
    """The tiny config, the reference, and its seeded weights (a non-zero
    selection bias), which are the program's parameters as they are."""
    cfg = tiny_cfg()
    ref = _reference()
    conf = ref_conf(cfg)
    return cfg, ref, conf, ref.make_weights(conf, jax.random.PRNGKey(3))


def test_published_sizes_and_the_cut():
    cfg = get_config("lfm2-8b-a1b")
    assert abs(cfg.param_count() - 8.34e9) < 0.01e9
    assert (cfg.n_attn_layers, cfg.n_conv_layers, cfg.n_moe_layers) == \
        (6, 18, 22)
    cut = dataclasses.replace(cfg, n_layers=11)
    assert abs(cut.param_count() - 3.56e9) < 0.01e9
    assert (cut.n_attn_layers, cut.n_conv_layers, cut.n_moe_layers) == \
        (3, 8, 9)
    assert [i for i, k in enumerate(cut.layer_kinds())
            if k == "full_attention"] == [2, 6, 10]


def test_prefill_then_paged_decode_tracks_the_reference(setup):
    """Bucketed prefill of three prompts, their pages and conv states
    written as the engine writes them, then 6 paged decode rounds (the
    paged attention and moe_experts kernels): each round's logits against
    the reference's full forward over the same tokens. In float32, so that
    the comparison is tight: at bfloat16 and these toy widths, rounding
    flips near-tied expert picks, which moves logits by a tenth of their
    range in the program's own full forward too."""
    _, ref, conf, w = setup
    cfg = tiny_cfg("float32")
    bs, max_len, nb = 8, 32, 16
    plens = np.array([5, 11, 16], np.int32)
    b = len(plens)
    rng = np.random.default_rng(0)
    toks = np.zeros((b, 16), np.int32)
    for i, p in enumerate(plens):
        toks[i, :p] = rng.integers(0, cfg.vocab, p)
    logits, fresh = T.prefill(w, jnp.asarray(toks), cfg, max_len,
                              prompt_lens=jnp.asarray(plens))
    paged = api.init_paged_cache(cfg, b, nb, bs, max_len, jnp.float32)
    table = np.zeros((b, max_len // bs), np.int32)
    free = list(rng.permutation(np.arange(1, nb)))
    for i in range(b):
        ids = [free.pop() for _ in range(max_len // bs)]
        table[i] = ids
        n = -(-int(plens[i]) // bs)
        paged = api.paged_write_prompt(cfg, paged, fresh, ids[:n], src=i)
        paged = api.write_slot_state(cfg, paged, fresh, i, src=i)
    paged["block_table"] = jnp.asarray(table)
    paged["len"] = jnp.asarray(plens)
    step = jax.jit(lambda p, t, c: T.decode_step(p, t, c, cfg))
    got = [np.asarray(logits[:, 0], np.float64)]
    fed = []
    for _ in range(6):
        tok = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
        fed.append(tok)
        logits, paged = step(w, jnp.asarray(tok[:, None]), paged)
        got.append(np.asarray(logits[:, 0], np.float64))
    assert (np.asarray(paged["len"]) == plens + 6).all()
    for i, p in enumerate(plens):
        # one padded length for every row (one reference program): the
        # reference is causal, so the padding never reaches these positions
        seq = np.zeros(32, np.int32)
        seq[:p + 6] = np.concatenate([toks[i, :p], [f[i] for f in fed]])
        want = np.asarray(ref.logits(conf, w, seq),
                          np.float64)[p - 1:p + 6]
        have = np.stack([g[i] for g in got])
        # float32 throughout; only summation orders differ (the kernels'
        # online softmax and tiles, the decode's one-token steps): ~1e-6 of
        # the logits' range. A dropped expert, a stale conv state or a
        # wrong page moves them by a tenth of it or more
        np.testing.assert_allclose(have, want,
                                   atol=1e-4 * np.abs(want).max(), rtol=0)


@pytest.mark.parametrize("block_tokens", [8, 256])
def test_moe_experts_kernel_drops_no_token(block_tokens):
    """Every token routed to expert 3 (its second pick random): the kernel
    against a dense masked jnp sum over all experts. At 8 rows a tile
    expert 3 spans several tiles; at 256 one. The capacity path would keep
    only ``_capacity`` of expert 3's 20 tokens."""
    from repro.kernels.moe_experts import moe_experts
    t, d, e, ff, k = 20, 128, 8, 256, 2
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e, ff, d)) / d ** 0.5,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, ff, d)) / ff ** 0.5, jnp.float32)
    second = np.array([rng.choice([j for j in range(e) if j != 3])
                       for _ in range(t)])
    ids = jnp.asarray(np.stack([np.full(t, 3), second], 1), jnp.int32)
    wts = jnp.asarray(rng.random((t, k)), jnp.float32)
    y, counts = moe_experts(x, ids, wts, wg, wu, wd,
                            block_tokens=block_tokens, block_ff=128)
    hi = jax.lax.Precision.HIGHEST
    g = jnp.einsum("td,efd->tef", x, wg, precision=hi)
    u = jnp.einsum("td,efd->tef", x, wu, precision=hi)
    ye = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, wd, precision=hi)
    mask = jnp.zeros((t, e)).at[jnp.arange(t)[:, None], ids].set(wts)
    want = jnp.einsum("te,ted->td", mask, ye, precision=hi)
    # float32 operands in interpret mode: only summation order differs
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    assert np.asarray(counts).tolist() == np.bincount(
        np.asarray(ids).ravel(), minlength=e).tolist()
    moe = dataclasses.replace(get_config("lfm2-8b-a1b").moe, num_experts=e,
                              top_k=k)
    assert _capacity(t, moe) < int(counts[3])


def test_selection_bias_picks_but_does_not_weight(setup):
    """The bias decides which experts are picked; the weights are the
    picked sigmoid scores alone, normalised. Against the reference's
    routing, and against what a bias leaking into the weights would give."""
    cfg, ref, conf, w = setup
    p = dict(w["layers"][3]["moe"])
    e = cfg.moe.num_experts
    p["bias"] = jnp.zeros((e,)).at[5].set(10.0)    # expert 5 always picked
    x = jax.random.normal(jax.random.PRNGKey(4), (32, cfg.d_model))
    wts, ids = route(x, p, cfg.moe)
    assert (np.asarray(ids) == 5).any(axis=1).all()
    dense = np.zeros((32, e))
    np.put_along_axis(dense, np.asarray(ids), np.asarray(wts), axis=1)
    m = ref.dims(conf)
    want = np.asarray(ref.routing(m, x, p["router"], p["bias"]))
    np.testing.assert_allclose(dense, want, atol=1e-6)
    scores = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    leaked = np.take_along_axis(scores + np.asarray(p["bias"]),
                                np.asarray(ids), 1)
    leaked /= leaked.sum(-1, keepdims=True)
    assert np.abs(np.asarray(wts) - leaked).max() > 0.1


@functools.lru_cache(maxsize=None)
def _prefill(cfg):
    return jax.jit(lambda w, t, n: T.prefill(w, t, cfg, 32, prompt_lens=n))


@pytest.mark.parametrize("plen", [1, 2, 3, 17])
def test_conv_state_after_bucketed_prefill(setup, plen):
    """The engine prefills right-padded to a power-of-two bucket (17 goes
    to 32); each short convolution's state must be ``B * x`` at the
    prompt's last two real positions (zeros before position 0), as the
    reference computes it from the prompt alone."""
    _, ref, conf, w = setup
    cfg = tiny_cfg("float32")
    bucket = 16 if plen <= 16 else 32
    toks = np.zeros((1, 32), np.int32)
    toks[0, :plen] = np.random.default_rng(plen).integers(0, cfg.vocab,
                                                            plen)
    _, cache = _prefill(cfg)(w, jnp.asarray(toks[:, :bucket]),
                             jnp.asarray([plen], jnp.int32))
    # the reference from the prompt alone: causal, so the zeros after it
    # (one padded length, one reference program) never reach its
    # positions; B * x at positions plen-2, plen-1 sits at plen, plen+1 of
    # the left-padded input
    _, convs = ref.hidden(conf, w, toks[0])
    have = np.asarray(cache["conv"][:, 0], np.float64)   # (L_conv, 2, d)
    want = np.stack([np.asarray(c)[plen:plen + 2] for c in convs])
    if plen == 1:
        assert not have[:, 0].any()
    # float32 throughout: summation order only
    np.testing.assert_allclose(have, want, atol=1e-5 * np.abs(want).max(),
                               rtol=0)


def _requests(n, plen, max_new):
    rng = np.random.default_rng(7)
    return [Request(uid=i, prompt=rng.integers(0, 128, plen + i).astype(
        np.int32), max_new_tokens=max_new) for i in range(n)]


def _drain(cfg, params, reqs, **kw):
    eng = Engine(cfg, params, ServeConfig(prefix_cache=False, **kw))
    for r in reqs:
        eng.submit(r)
    return eng, {r.uid: list(r.out_tokens)
                 for r in eng.run_until_drained()}


def test_paged_drain_with_preemption_replays_the_same_streams(setup):
    """The minimum legal pool (one max_len sequence) forces mid-decode
    preemption; the preempted request is prefilled again, which rebuilds
    its conv state, and every stream matches an ample pool's."""
    cfg, _, _, w = setup
    kw = dict(max_batch=2, max_len=32, kv_layout="paged", kv_block_size=8)
    _, want = _drain(cfg, w, _requests(3, 9, 16), **kw)
    eng, got = _drain(cfg, w, _requests(3, 9, 16), kv_num_blocks=5, **kw)
    assert got == want
    assert eng.stats["prefills"] > 3          # somebody was preempted


def test_engine_counts_every_expert_assignment(setup):
    """``serve.moe_assignments`` is top_k x expert layers x (every decode
    round's max_batch rows + every prefill's bucket); experts hit never
    exceed the experts of those calls."""
    cfg, _, _, w = setup
    reqs = _requests(3, 5, 4)
    eng, _ = _drain(cfg, w, reqs, max_batch=2, max_len=32,
                    kv_layout="paged", kv_block_size=8)
    m = eng.metrics
    rows = eng.stats["decode_steps"] * 2 + sum(
        eng._bucket_len(len(r.prompt)) for r in reqs)
    calls = (eng.stats["decode_steps"] + eng.stats["prefills"]) \
        * cfg.n_moe_layers
    assert m.counter("serve.moe_assignments").value == \
        cfg.moe.top_k * cfg.n_moe_layers * rows
    assert 0 < m.counter("serve.moe_experts_hit").value \
        <= calls * cfg.moe.num_experts


def test_retirement_clears_the_conv_state(setup):
    cfg = setup[0]
    cache = api.init_paged_cache(cfg, 3, 8, 8, 32)
    cache["conv"] = jnp.ones_like(cache["conv"])
    freed = api.cache_free_slot(cache, 1)
    conv = np.asarray(freed["conv"])
    assert not conv[:, 1].any() and conv[:, [0, 2]].all()


def test_engine_refuses_prefix_cache_with_conv_layers(setup):
    cfg, _, _, w = setup
    with pytest.raises(NotImplementedError, match="prefix_cache"):
        Engine(cfg, w, ServeConfig(kv_layout="paged"))
    with pytest.raises(NotImplementedError, match="kv_cache"):
        Engine(cfg, w, ServeConfig(kv_layout="paged", prefix_cache=False,
                                   kv_cache="int8"))
    Engine(cfg, w, ServeConfig(kv_layout="paged", prefix_cache=False))
