"""Ahead-of-time compiles of the serving paths' Pallas kernels for a TPU v5e.

Each case compiles one kernel with ``interpret=False`` for a described (not
attached) v5e chip, at the shapes the paper's CNN (default ``CNNConfig``,
batch 8), qwen2-0.5b's FFN (896 x 4864) and its paged decode attention (64
slots, 8193 pages of 16 x 128 bf16 per layer) serve, under the schedule the
tuner resolves for that shape. The chip's compiler then refuses what
interpret mode accepts: unaligned blocks, unsupported vector ops, VMEM
overruns. Nothing runs; results are checked by the interpret-mode tests.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import tune
from repro.kernels.conv1d_causal import causal_conv1d
from repro.kernels.conv_add import add_conv2d
from repro.kernels.conv_dw import depthwise2d
from repro.kernels.conv_im2col import conv2d_im2col
from repro.kernels.conv_shift import shift_conv2d
from repro.kernels.matmul_q8 import matmul
from repro.kernels.moe_experts import moe_experts
from repro.kernels.paged_attention import paged_attention
from repro.kernels.pool import maxpool2d

N = 8                                   # CNNEngine's max_batch bucket
I8, I32 = jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_hlo(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _shifts(c):
    i = np.arange(c)
    return np.stack([i % 3 - 1, (i // 3) % 3 - 1], axis=1)


# (name, builder) — a builder returns (kernel fn, [(shape, dtype), ...]) at
# one of the CNN's three layer shapes (32x32x3->16, 16x16x16->32,
# 8x8x32->64) or the LM FFN's widths; schedules are the tuner's picks
def _conv(h, ci, co, groups=1, w4=False):
    sig = tune.sig_conv2d(N, h, h, ci, co, 3, groups)
    cfg = tune.get_config(sig, "w4a8" if w4 else "int8")
    cxg = ci // groups
    if w4:
        return (lambda x, w, ws, b: conv2d_im2col(
            x, w, b, groups=groups, requant_shift=6, act="relu",
            interpret=False, config=cfg, w_shifts=ws),
            [((N, h, h, ci), I8), ((3, 3, (cxg + 1) // 2, co), I8),
             ((cxg,), I8), ((co,), I32)])
    return (lambda x, w, b: conv2d_im2col(
        x, w, b, groups=groups, requant_shift=6, act="relu",
        interpret=False, config=cfg),
        [((N, h, h, ci), I8), ((3, 3, cxg, co), I8), ((co,), I32)])


def _dws(h, c):
    cfg = tune.get_config(tune.sig_depthwise2d(N, h, h, c, 3), "int8")
    return (lambda x, w: depthwise2d(x, w, requant_shift=6, interpret=False,
                                     config=cfg),
            [((N, h, h, c), I8), ((3, 3, c), I8)])


def _shift(h, c, co):
    cfg = tune.get_config(tune.sig_shift_conv2d(N, h, h, c, co), "int8")
    return (lambda x, w, b: shift_conv2d(x, _shifts(c), w, b, requant_shift=6,
                                         act="relu", interpret=False,
                                         config=cfg),
            [((N, h, h, c), I8), ((c, co), I8), ((co,), I32)])


def _add(h, ci, co):
    cfg = tune.get_config(tune.sig_add_conv2d(N, h, h, ci, co, 3), "int8")
    return (lambda x, w, b: add_conv2d(x, w, b, requant_shift=6, act="relu",
                                       interpret=False, config=cfg),
            [((N, h, h, ci), I8), ((3, 3, ci, co), I8), ((co,), I32)])


def _pool(h, c):
    cfg = tune.get_config(tune.sig_maxpool2d(N, h, h, c, 2, 2), "int8")
    return (lambda x: maxpool2d(x, window=2, interpret=False, config=cfg),
            [((N, h, h, c), I8)])


def _mm(m, k, n, w4=False):
    cfg = tune.get_config(tune.sig_matmul(m, k, n), "w4a8" if w4 else "int8")
    if w4:
        return (lambda a, b, ws: matmul(a, b, requant_shift=6,
                                        interpret=False, config=cfg,
                                        w_shifts=ws),
                [((m, k), I8), (((k + 1) // 2, n), I8), ((k,), I8)])
    return (lambda a, b: matmul(a, b, requant_shift=6, interpret=False,
                                config=cfg),
            [((m, k), I8), ((k, n), I8)])


# the qwen2-0.5b bf16 cell: 24 layers, 8193 pages of 16 positions x
# (2 kv heads x 64), 64 slots of up to 128 pages, 14 query heads
POOL = (24, 8193, 16, 128)


def _paged_attention():
    return (lambda q, k, v, layer, table, lens: paged_attention(
        q, k, v, layer, table, lens, interpret=False),
        [((64, 14, 64), jnp.bfloat16), (POOL, jnp.bfloat16),
         (POOL, jnp.bfloat16), ((), I32), ((64, 128), I32), ((64,), I32)])


# the lfm2-8b-a1b-11l-bf16 cell: 32 experts of (1792, 2048) bf16 matrices,
# top-4; a decode round routes 64 tokens, a prefill up to a 1024 bucket;
# short convolutions of 3 taps over 2048 channels
EXPERTS = (32, 1792, 2048)


def _moe_experts(tokens):
    return (lambda x, ids, w, wg, wu, wd: moe_experts(
        x, ids, w, wg, wu, wd, interpret=False),
        [((tokens, 2048), jnp.bfloat16), ((tokens, 4), I32),
         ((tokens, 4), jnp.float32), (EXPERTS, jnp.bfloat16),
         (EXPERTS, jnp.bfloat16), (EXPERTS, jnp.bfloat16)])


def _conv1d(length):
    return (lambda x, w: causal_conv1d(x, w, interpret=False),
            [((1, length, 2048), jnp.bfloat16), ((3, 2048), jnp.bfloat16)])


CASES = {
    "conv2d_int8_stem": lambda: _conv(32, 3, 16),
    "conv2d_int8": lambda: _conv(16, 16, 32),
    "conv2d_int8_pointwise": lambda: _conv(8, 32, 64),
    "conv2d_grouped_int8": lambda: _conv(16, 16, 32, groups=2),
    "depthwise2d_int8": lambda: _dws(16, 16),
    "shift_conv2d_int8": lambda: _shift(16, 16, 32),
    "add_conv2d_int8_stem": lambda: _add(32, 3, 16),
    "add_conv2d_int8": lambda: _add(8, 32, 64),
    "conv2d_w4": lambda: _conv(16, 16, 32, w4=True),
    "maxpool2d_int8": lambda: _pool(32, 16),
    "matmul_q8_ffn_up": lambda: _mm(N, 896, 4864),
    "matmul_q8_ffn_down": lambda: _mm(N, 4864, 896),
    "matmul_w4_ffn_up": lambda: _mm(N, 896, 4864, w4=True),
    "paged_attention_decode": _paged_attention,
    "moe_experts_decode": lambda: _moe_experts(64),
    "moe_experts_prefill": lambda: _moe_experts(1024),
    "conv1d_causal_prefill": lambda: _conv1d(1024),
    "conv1d_causal_short": lambda: _conv1d(16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    hlo = _compiled_hlo(one_chip, fn, *shapes)
    assert "tpu_custom_call" in hlo


def _engine_decode_hlo(cfg, one_chip):
    """The paged decode step as the engine jits it at the cells' shapes
    (64 slots, 8193 pages of 16 positions, max_len 2048): the device-pick
    wrapper, cache donated. Returns the compiled HLO text and the cache's
    shapes, after checking that the module's outputs are the (64,) int32
    ids, the (64, 1, vocab) float32 logits and the cache."""
    from repro.models import api
    put = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = put(api.param_structs(cfg))
    cache = put(jax.eval_shape(
        lambda: api.init_paged_cache(cfg, 64, 8193, 16, 2048)))
    tok = jax.ShapeDtypeStruct((64, 1), I32, sharding=one_chip)
    lowered = jax.jit(api.decode_pick_fn(cfg), donate_argnums=(2,)).lower(
        params, tok, cache)
    ids, logits, out_cache = lowered.out_info
    assert (ids.shape, ids.dtype) == ((64,), jnp.int32)
    assert (logits.shape, logits.dtype) == ((64, 1, cfg.vocab), jnp.float32)
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), out_cache) \
        == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), cache)
    hlo = lowered.compile().as_text()
    entry = hlo[hlo.index("\nENTRY "):]
    root = re.search(r"^\s*ROOT .*$", entry, re.M).group(0)
    assert re.search(rf"= \(s32\[64\]\{{[^}}]*\}}, f32\[64,1,{cfg.vocab}\]",
                     root), root[:200]
    return hlo, cache


def _pool_sized_moves(hlo):
    """Operations outside the kernels that produce a pool-sized (8193-page)
    or table-sized (8192-page) array, the in-place scatters excepted."""
    op = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
    big = []
    for line in hlo.splitlines():
        m = op.match(line)
        if m and {"8193", "8192"} & set(m.group(1).split(",")):
            big.append((m.group(2), "scatter" in line))
    assert big
    return [kind for kind, scatter in big if not scatter and kind not in
            ("parameter", "get-tuple-element", "bitcast")]


def test_paged_decode_step_moves_no_pool(one_chip, monkeypatch):
    """The whole paged decode step of qwen2-0.5b, as the engine jits it
    (the device-pick wrapper, cache donated): the kernel reads the stacked
    pools in place and the new K/V is scattered into them, so no operation
    outside the kernel produces a pool-sized (8193-page) or table-sized
    (8192-page) array except the in-place scatters; the module returns the
    s32[64] ids beside the logits and the cache."""
    from repro.configs import get_config
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    hlo, cache = _engine_decode_hlo(get_config("qwen2-0.5b"), one_chip)
    assert cache["k"].shape == POOL
    assert "paged_attention" in hlo and "tpu_custom_call" in hlo
    moves = _pool_sized_moves(hlo)
    assert not moves, moves


def test_lfm2_paged_decode_step_moves_no_pool(one_chip, monkeypatch):
    """The whole paged decode step of the lfm2-8b-a1b cell (its 11 layers:
    8 short convolutions, 3 attention layers over (3, 8193, 16, 512) bf16
    pools, 9 dropless expert layers), as the engine jits it: the expert
    layers run the moe_experts kernel, no operation outside the kernels
    produces a pool-sized or table-sized array but the in-place scatters,
    and the module returns the s32[64] ids beside the logits and the
    cache."""
    import dataclasses
    from repro.configs import get_config
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cfg = dataclasses.replace(get_config("lfm2-8b-a1b"), n_layers=11)
    hlo, cache = _engine_decode_hlo(cfg, one_chip)
    assert cache["k"].shape == (3, 8193, 16, 512)
    assert cache["conv"].shape == (8, 64, 2, 2048)
    assert "paged_attention" in hlo and "moe_experts" in hlo
    moves = _pool_sized_moves(hlo)
    assert not moves, moves
