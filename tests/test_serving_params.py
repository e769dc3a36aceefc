"""``api.serving_params``: the matmul weights the serving step reads only
through ``.astype(compute_dtype)`` are cast once, at ``Engine``
construction. The step's logits and picks are bit-identical with and
without the cast (prefill, suffix-only prefill, paged decode, whole
drains), leaves read at another precision stay as they are, a tree
already in the compute dtype is not copied, and the caller's tree and
the PTQ input are untouched."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.models.blocks import quantize_mlp_params
from repro.serve import Engine, Request, ServeConfig


def dense_cfg():
    """qwen2's GQA with qkv biases, float32 weights, bfloat16 compute."""
    return dataclasses.replace(get_config("qwen2-0.5b"), n_layers=2,
                               d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                               vocab=64)


def lfm2_cfg():
    """LFM2's first 7 layers (conv, conv, attention, conv, conv, conv,
    attention; 2 dense FFNs, then dropless experts) at toy widths."""
    cfg = get_config("lfm2-8b-a1b")
    return dataclasses.replace(
        cfg, n_layers=7, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96,
        vocab=64, param_dtype="float32", compute_dtype="bfloat16",
        moe=dataclasses.replace(cfg.moe, num_experts=8, top_k=2, d_ff=32))


def moe_cfg():
    """A capacity-dispatched MoE stack (granite-moe) at toy widths."""
    cfg = get_config("granite-moe-1b-a400m")
    return dataclasses.replace(
        cfg, n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_ff=32,
        vocab=64, moe=dataclasses.replace(cfg.moe, num_experts=4, top_k=2,
                                          d_ff=16))


CFGS = {"dense": dense_cfg, "lfm2": lfm2_cfg, "moe": moe_cfg}


@functools.lru_cache(maxsize=None)
def _setup(name):
    cfg = CFGS[name]()
    assert (cfg.param_dtype, cfg.compute_dtype) == ("float32", "bfloat16")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    # non-zero qkv and expert biases and norm weights off 1, none of them
    # exact in bfloat16, so that a cast of any of them would show
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.02 * jax.random.normal(
            jax.random.PRNGKey(len(path)), x.shape, x.dtype)
        if path[-1].key in ("bq", "bk", "bv", "bias", "ln1", "ln2",
                            "final_norm") else x, params)
    return cfg, params


@pytest.fixture(params=sorted(CFGS))
def setup(request):
    return _setup(request.param)


def _leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


def _prompts(cfg, plens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, p).astype(np.int32) for p in plens]


def _prefill_and_decode(cfg, params, rounds=3):
    """A bucketed prefill of three prompts, their pages (and conv states)
    written as the engine writes them, then ``rounds`` paged decode
    rounds through ``decode_pick_fn``: every logits array and pick."""
    bs, max_len, nb = 8, 32, 16
    plens = np.array([5, 11, 16], np.int32)
    b = len(plens)
    toks = np.zeros((b, 16), np.int32)
    for i, p in enumerate(_prompts(cfg, plens)):
        toks[i, :len(p)] = p
    logits, fresh = jax.jit(api.prefill_fn(cfg, max_len))(
        params, {"tokens": jnp.asarray(toks),
                 "prompt_lens": jnp.asarray(plens)})
    paged = api.init_paged_cache(cfg, b, nb, bs, max_len)
    table = np.arange(1, nb, dtype=np.int32)[:b * (max_len // bs)]
    table = table.reshape(b, max_len // bs)
    for i in range(b):
        n = -(-int(plens[i]) // bs)
        paged = api.paged_write_prompt(cfg, paged, fresh, table[i, :n], src=i)
        paged = api.write_slot_state(cfg, paged, fresh, i, src=i)
    paged["block_table"] = jnp.asarray(table)
    paged["len"] = jnp.asarray(plens)
    step = jax.jit(api.decode_pick_fn(cfg))
    out = [np.asarray(logits)]
    tok = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
    for _ in range(rounds):
        ids, logits, paged = step(params, jnp.asarray(tok[:, None]), paged)
        out += [np.asarray(ids), np.asarray(logits)]
        tok = np.asarray(ids)
    return out


def test_step_is_bit_identical_with_the_cast(setup):
    """Prefill logits, then each paged decode round's picks and logits:
    the same bits from the cast tree as from the float32 tree."""
    cfg, params = setup
    want = _prefill_and_decode(cfg, params)
    got = _prefill_and_decode(cfg, api.serving_params(cfg, params))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_prefill_suffix_is_bit_identical_with_the_cast(name):
    """The prefix-hit path: the suffix's logits and K/V against a donor's
    prefix K/V, from both trees (a pattern stack has no suffix prefill:
    its engine refuses the prefix cache)."""
    cfg, params = _setup(name)
    prompt, = _prompts(cfg, [21], seed=11)
    pfx = 16

    def run(p):
        toks = np.asarray(prompt[:pfx])[None]
        _, donor = jax.jit(api.prefill_fn(cfg, pfx))(
            p, {"tokens": jnp.asarray(toks),
                "prompt_lens": jnp.asarray([pfx], jnp.int32)})
        stoks = np.zeros((1, 8), np.int32)
        stoks[0, :len(prompt) - pfx] = prompt[pfx:]
        out = jax.jit(api.prefill_suffix_fn(cfg))(p, {
            "tokens": jnp.asarray(stoks), "prefix_k": donor["k"],
            "prefix_v": donor["v"],
            "suffix_lens": jnp.asarray([len(prompt) - pfx], jnp.int32)})
        return [np.asarray(x) for x in out]

    for g, w in zip(run(api.serving_params(cfg, params)), run(params)):
        np.testing.assert_array_equal(g, w)


def _requests(cfg):
    """Five requests, the last three sharing a two-page prompt prefix (a
    prefix hit where the engine keeps a prefix cache)."""
    shared, = _prompts(cfg, [16], seed=5)
    prompts = _prompts(cfg, [7, 12], seed=3) + [
        np.concatenate([shared, p]) for p in _prompts(cfg, [3, 5, 9],
                                                      seed=4)]
    return [Request(uid=i, prompt=p, max_new_tokens=6 + i)
            for i, p in enumerate(prompts)]


def test_engine_streams_identical_with_and_without_the_cast(setup,
                                                            monkeypatch):
    """Whole paged drains: an Engine serving the float32 tree as it is
    (``serving_params`` replaced by the identity) and the Engine as built
    give the same token streams; only the latter made bf16 copies."""
    cfg, params = setup
    kw = dict(max_batch=2, max_len=64, kv_layout="paged", kv_block_size=8,
              prefix_cache=not cfg.layer_types)

    def drain():
        eng = Engine(cfg, params, ServeConfig(**kw))
        for r in _requests(cfg):
            eng.submit(r)
        done = eng.run_until_drained()
        assert all(r.status == "ok" for r in done)
        return eng, {r.uid: list(r.out_tokens) for r in done}

    eng, want = drain()
    cast = eng.metrics.gauge("serve.cast_weight_bytes").value
    with monkeypatch.context() as m:
        m.setattr(api, "serving_params", lambda cfg, p: p)
        raw, got = drain()
    assert got == want
    assert raw.metrics.gauge("serve.cast_weight_bytes").value == 0
    assert cast > 0
    if not cfg.layer_types:
        assert eng.stats["prefix_hit_rate"] > 0     # the suffix path ran


# ---------------------------------------------------------- leaf policy --

KEPT = {"embed", "unembed", "ln1", "ln2", "final_norm", "router", "bias",
        "q_norm", "k_norm"}


def test_leaf_policy(setup):
    """The matmul weights and qkv biases come back in bfloat16, rounded
    as the step rounds them; embed, norms, router and expert bias stay the
    same float32 objects; the gauge counts exactly the copies' bytes."""
    cfg, params = setup
    served = api.serving_params(cfg, params)
    assert jax.tree_util.tree_structure(served) == \
        jax.tree_util.tree_structure(params)
    cast_bytes, seen = 0, set()
    for (path, x), (_, y) in zip(_leaves(params), _leaves(served)):
        key = path[-1].key
        seen.add(key)
        if key in KEPT:
            assert y is x and y.dtype == jnp.float32, key
        else:
            assert y.dtype == jnp.bfloat16 and y.shape == x.shape, key
            np.testing.assert_array_equal(
                np.asarray(y), np.asarray(x.astype(jnp.bfloat16)))
            cast_bytes += y.nbytes
    assert {"embed", "ln1", "final_norm"} <= seen
    assert {"wq", "wo", "w_up", "w_down"} <= seen
    if cfg.moe is not None:
        assert "router" in seen
    eng = Engine(cfg, params, ServeConfig(
        max_batch=2, max_len=32, kv_layout="paged", kv_block_size=8,
        prefix_cache=False))
    assert eng.metrics.gauge("serve.cast_weight_bytes").value == cast_bytes
    eng.reset_stats()
    assert eng.metrics.gauge("serve.cast_weight_bytes").value == cast_bytes


def test_compute_dtype_tree_is_not_copied(setup):
    """A tree stored in the compute dtype (as LFM2 serves it) comes back
    with every leaf the same object, and the engine copies nothing."""
    cfg, params = setup
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    bf16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    served = api.serving_params(cfg, bf16)
    for x, y in zip(jax.tree_util.tree_leaves(bf16),
                    jax.tree_util.tree_leaves(served)):
        assert y is x
    eng = Engine(cfg, bf16, ServeConfig(
        max_batch=2, max_len=32, kv_layout="paged", kv_block_size=8,
        prefix_cache=False))
    assert eng.metrics.gauge("serve.cast_weight_bytes").value == 0
    assert all(y is x for x, y in zip(jax.tree_util.tree_leaves(bf16),
                                      jax.tree_util.tree_leaves(eng.params)))


def test_engine_leaves_the_callers_tree_as_it_was(setup):
    """``Engine(...)`` keeps the caller's tree: the same dicts, lists and
    leaves, in their dtypes (the benchmark's reference reads them)."""
    cfg, params = setup
    before = [(p, x, x.dtype) for p, x in _leaves(params)]
    structure = jax.tree_util.tree_structure(params)
    eng = Engine(cfg, params, ServeConfig(max_batch=2, max_len=32,
                                          kv_layout="paged",
                                          kv_block_size=8,
                                          prefix_cache=False))
    assert eng.params is not params
    assert jax.tree_util.tree_structure(params) == structure
    after = _leaves(params)
    assert len(after) == len(before)
    for (p, x, dt), (q, y) in zip(before, after):
        assert p == q and y is x and y.dtype == dt


@pytest.mark.parametrize("precision", ["int8", "w4a8"])
def test_ptq_reads_the_float32_ffn_weights(precision):
    """The int8 / w4a8 PTQ runs before the cast, on the float32 FFN
    weights: ``qmlp`` is what ``quantize_mlp_params`` gives on them."""
    cfg = dense_cfg()
    params = api.init_params(cfg, jax.random.PRNGKey(1))
    eng = Engine(cfg, params, ServeConfig(max_batch=2, max_len=32,
                                          precision=precision))
    want = quantize_mlp_params(params["layers"]["mlp"],
                               bits=4 if precision == "w4a8" else 8)
    got = eng.params["layers"]["qmlp"]
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert params["layers"]["mlp"]["w_up"].dtype == jnp.float32
