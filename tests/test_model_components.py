"""Component tests: attention (flash vs full, GQA, decode/SP math), MoE
dispatch invariants, mamba scan vs naive recurrence, chunked CE, rooofline
HLO parser, energy model claims."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need hypothesis
from hypothesis import given, settings, strategies as st

from repro.models import attention as A

KEY = jax.random.PRNGKey(0)


# -------------------------------------------------------------- attention --
@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (4, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_full(hq, hkv, causal):
    q = jax.random.normal(KEY, (2, 24, hq, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 24, hkv, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 24, hkv, 16))
    o1 = A.flash_attention(q, k, v, causal=causal, block_k=8)
    o2 = A.full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)


def test_flash_grads_match_full():
    q = jax.random.normal(KEY, (1, 16, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 8))
    f1 = lambda *a: jnp.sum(jnp.tanh(A.flash_attention(*a, causal=True, block_k=4)))
    f2 = lambda *a: jnp.sum(jnp.tanh(A.full_attention(*a, causal=True)))
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_decode_attention_matches_full_last_row():
    q = jax.random.normal(KEY, (2, 1, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 2, 8))
    # pad cache beyond valid length; decode must mask it
    kp = jnp.pad(k, ((0, 0), (0, 4), (0, 0), (0, 0)), constant_values=9.0)
    vp = jnp.pad(v, ((0, 0), (0, 4), (0, 0), (0, 0)), constant_values=9.0)
    got = A.decode_attention(q, kp, vp, jnp.array(12))
    want = A.full_attention(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sp_combine_equals_unsharded():
    """Split the KV cache into 4 'shards', combine partials -> same output."""
    q = jax.random.normal(KEY, (1, 1, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 8))
    want = A.decode_attention(q, k, v, jnp.array(16))

    ms, ls, os_ = [], [], []
    for i in range(4):
        ksh, vsh = k[:, i * 4:(i + 1) * 4], v[:, i * 4:(i + 1) * 4]
        m, l, o = A.decode_attention_partial(q, ksh, vsh,
                                             jnp.ones(4, bool))
        ms.append(m), ls.append(l), os_.append(o)
    m_glob = jnp.max(jnp.stack(ms), 0)
    corr = [jnp.exp(m - m_glob) for m in ms]
    l_glob = sum(l * c for l, c in zip(ls, corr))
    o_glob = sum(o * c[..., None] for o, c in zip(os_, corr)) / l_glob[..., None]
    got = jnp.moveaxis(o_glob, 3, 1).reshape(1, 1, 4, 8)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------------- moe --
def test_moe_matches_dense_when_capacity_ample():
    from repro.configs.base import MoEConfig
    from repro.models.moe import init_moe, moe_ffn_local
    moe = MoEConfig(num_experts=4, top_k=2, d_ff=16, capacity_factor=8.0)
    p = init_moe(KEY, 8, moe, "silu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 6, 8))
    got = moe_ffn_local(x, p, moe, "silu", jnp.float32)
    # dense reference: weight every expert by its softmaxed top-k prob
    logits = x.reshape(-1, 8) @ p["router"]
    k_v, k_i = jax.lax.top_k(logits, 2)
    probs = jax.nn.softmax(k_v, -1)
    dense = np.zeros((12, 8), np.float32)
    for t in range(12):
        for j in range(2):
            e = int(k_i[t, j])
            h = x.reshape(-1, 8)[t] @ p["w_up"][e]
            g = x.reshape(-1, 8)[t] @ p["w_gate"][e]
            z = jax.nn.silu(g) * h
            dense[t] += float(probs[t, j]) * np.asarray(z @ p["w_down"][e])
    np.testing.assert_allclose(got.reshape(12, 8), dense, rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_tokens():
    from repro.configs.base import MoEConfig
    from repro.models.moe import init_moe, moe_ffn_local
    moe = MoEConfig(num_experts=2, top_k=1, d_ff=8, capacity_factor=0.5)
    p = init_moe(KEY, 4, moe, "silu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 4))
    y = moe_ffn_local(x, p, moe, "silu", jnp.float32)
    # capacity = 2 per expert; at most 4 of 8 tokens get outputs
    nz = jnp.sum(jnp.any(jnp.abs(y) > 1e-9, axis=-1))
    assert int(nz) <= 4


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 100))
def test_moe_combine_weights_sum_bounded(seed):
    """Each token's combine weights are a softmax subset: output norm is
    bounded by max expert output norm."""
    from repro.configs.base import MoEConfig
    from repro.models.moe import route
    x = jax.random.normal(jax.random.PRNGKey(seed), (16, 8))
    router = jax.random.normal(jax.random.PRNGKey(seed + 1), (8, 4))
    probs, ids = route(x, {"router": router},
                       MoEConfig(num_experts=4, top_k=2, d_ff=8))
    assert probs.shape == (16, 2)
    np.testing.assert_allclose(jnp.sum(probs, -1), 1.0, rtol=1e-5)
    assert int(jnp.max(ids)) < 4


# ------------------------------------------------------------------ mamba --
def test_mamba_scan_matches_naive_recurrence():
    from repro.models.mamba import mamba_scan
    b, l, di, n = 2, 12, 4, 3
    x = jax.random.normal(KEY, (b, l, di))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (b, l, di)))
    Amat = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (di, n)))
    Bt = jax.random.normal(jax.random.PRNGKey(3), (b, l, n))
    Ct = jax.random.normal(jax.random.PRNGKey(4), (b, l, n))
    y, h_last = mamba_scan(x, dt, Amat, Bt, Ct, chunk=4)

    h = np.zeros((b, di, n), np.float32)
    ys = np.zeros((b, l, di), np.float32)
    for t in range(l):
        a = np.exp(np.asarray(dt)[:, t, :, None] * np.asarray(Amat)[None])
        bx = (np.asarray(dt)[:, t] * np.asarray(x)[:, t])[:, :, None] \
            * np.asarray(Bt)[:, t, None, :]
        h = a * h + bx
        ys[:, t] = np.einsum("bdn,bn->bd", h, np.asarray(Ct)[:, t])
    np.testing.assert_allclose(y, ys, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_last, h, rtol=2e-4, atol=2e-4)


def test_mamba_decode_matches_forward():
    import dataclasses
    from repro.configs.base import MambaConfig
    from repro.models.mamba import (init_mamba, mamba_decode_step,
                                    mamba_init_state)
    from repro.models.transformer import _mamba_forward_with_state
    m = MambaConfig(d_state=4, d_conv=3, expand=2)
    p = init_mamba(KEY, 8, m, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 6, 8)) * 0.3
    y_full, state = _mamba_forward_with_state(p, x, m, jnp.float32)
    # decode token-by-token must reproduce the full forward
    st_ = {"conv": jnp.zeros((2, m.d_conv - 1, 16)),
           "ssm": jnp.zeros((2, 16, 4))}
    outs = []
    for t in range(6):
        y_t, st_ = mamba_decode_step(p, x[:, t:t + 1], st_, m, jnp.float32)
        outs.append(y_t)
    y_step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(y_step, y_full, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(st_["ssm"], state["ssm"], rtol=2e-3, atol=2e-3)


# ------------------------------------------------------------- chunked CE --
def test_chunked_ce_matches_full():
    from repro.models.blocks import chunked_softmax_ce, cross_entropy
    h = jax.random.normal(KEY, (2, 10, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 10), 0, 32)
    labels = labels.at[0, :2].set(-1)           # masked positions
    got = chunked_softmax_ce(h, w, labels, chunk=3, z_loss=0.0)
    want = cross_entropy(h @ w, labels)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_chunked_ce_grad_matches():
    from repro.models.blocks import chunked_softmax_ce, cross_entropy
    h = jax.random.normal(KEY, (1, 8, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    labels = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, 16)
    g1 = jax.grad(lambda ww: chunked_softmax_ce(h, ww, labels, chunk=4,
                                                z_loss=0.0))(w)
    g2 = jax.grad(lambda ww: cross_entropy(h @ ww, labels))(w)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-6)


# --------------------------------------------------------- roofline parser --
def test_hlo_parser_scan_trip_counts():
    from jax import lax
    from repro.roofline.hlo import analyze_hlo
    def f(x, w):
        y, _ = lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x, None, length=7)
        return y
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32),
                         jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    agg = analyze_hlo(c.as_text())
    assert agg["dot_flops"] == 7 * 2 * 64 ** 3


def test_hlo_parser_iota_replica_groups():
    from repro.roofline.hlo import _crosses_pod, _iota_groups
    g = _iota_groups("[2,4]<=[8]")
    np.testing.assert_array_equal(g, [[0, 1, 2, 3], [4, 5, 6, 7]])
    assert _crosses_pod("replica_groups=[2,4]<=[8]", pod_size=4) is False
    # [4,2]<=[2,4]T(1,0): groups {0,4},{1,5},{2,6},{3,7} — stride-4 pairs
    g2 = _iota_groups("[4,2]<=[2,4]T(1,0)")
    np.testing.assert_array_equal(g2, [[0, 4], [1, 5], [2, 6], [3, 7]])
    assert _crosses_pod("replica_groups=[4,2]<=[2,4]T(1,0)", pod_size=4) is True
    assert _crosses_pod("replica_groups={{0,1},{2,3}}", pod_size=2) is False
    assert _crosses_pod("replica_groups={{0,2},{1,3}}", pod_size=2) is True


# ---------------------------------------------------------------- energy ---
def test_energy_model_reproduces_paper_claims():
    from repro.core import ConvSpec, MCUModel, reuse_ratio
    from benchmarks.common import r_squared
    mcu = MCUModel()
    specs, macs, e_scalar, lat_simd, e_simd = [], [], [], [], []
    for hk in (1, 3, 5, 7):
        for cx in (4, 8, 16):
            s = ConvSpec(primitive="standard", in_channels=cx, out_channels=16,
                         kernel_size=hk, use_bias=False)
            macs.append(s.mac_count(32))
            e_scalar.append(mcu.energy_mj(s, 32, simd=False))
            lat_simd.append(mcu.latency_s(s, 32, simd=True))
            e_simd.append(mcu.energy_mj(s, 32, simd=True))
    r2_scalar = r_squared(macs, e_scalar)
    r2_simd_macs = r_squared(macs, e_simd)
    r2_simd_lat = r_squared(lat_simd, e_simd)
    assert r2_scalar > 0.99                       # paper: 0.995-0.999
    assert r2_simd_lat > r2_simd_macs - 1e-9      # latency predicts better
    # Table 3: max frequency minimizes energy
    e = [mcu.energy_mj(ConvSpec(in_channels=3, out_channels=32), 32,
                       simd=True, f_mhz=f) for f in (10, 20, 40, 80)]
    assert e[-1] == min(e)
    # Fig 3: shift conv has higher reuse ratio than standard at same shape
    r_std = reuse_ratio(ConvSpec(in_channels=16, out_channels=16), 32)
    assert r_std > 1.0
