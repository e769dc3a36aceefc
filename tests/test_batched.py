"""Batched / spatially-tiled kernel schedules + the throughput serving path.

Pins the contracts of the tiled-grid rewrite:
  * batched-vs-looped bit-exactness for every primitive + matmul (int8 AND
    float), on odd H/W (ragged halo tiles) and non-pow2 N (ragged batch
    blocks) under explicit block_n/block_h/block_w schedules;
  * ``CompiledPlan.forward_batch`` == the per-sample loop (int8 trunk
    bit-exact; float head at tight tolerance) and compiles once per pow2
    batch bucket (compile-count asserted);
  * the v2 tune space carries the new knobs, resolves them through the
    same ``batch_spatial_schedule`` the kernels run, and refuses v1 caches;
  * ``repro.serve.CNNEngine`` admits queued image requests into batch
    rounds and returns every request's logits.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.core import Primitives
from repro.core.quantize import QTensor, quantize
from repro.graph import CompiledPlan, build_cnn_graph, lower
from repro.kernels import ops
from repro.kernels.conv_add import add_conv2d
from repro.kernels.conv_dw import depthwise2d
from repro.kernels.conv_im2col import conv2d_im2col
from repro.kernels.conv_shift import shift_conv2d
from repro.kernels.matmul_q8 import matmul
from repro.kernels.pool import maxpool2d
from repro.models.convnet import CNNConfig, init_cnn

KEY = jax.random.PRNGKey(0)

# non-pow2 batch and odd H/W: exercises ragged batch blocks (block_n=4 on
# N=5 degrades through effective_block) and ragged final halo tiles
N, H, W = 5, 9, 7


def rnd(shape, dtype=jnp.float32, key=KEY):
    if jnp.issubdtype(dtype, jnp.integer):
        return jax.random.randint(key, shape, -100, 100, jnp.int32).astype(dtype)
    return jax.random.normal(key, shape).astype(dtype)


@pytest.fixture(autouse=True)
def _clean_tuner_state():
    tune.set_default_cache(tune.TuneCache(None))
    yield
    tune.reset()


# ----------------------------------------- kernel-level batched == looped --

TILED_CFG = {"block_n": 4, "block_h": 4, "block_w": 4}


def _assert_batched_equals_looped(fn, x, *args, cfg, **kw):
    """fn(batch, config=tiled) must equal the per-image loop at the default
    (untiled) schedule, bitwise — the tiled grid reorders DMA, never math."""
    got = fn(x, *args, config=cfg, **kw)
    loop = jnp.concatenate([fn(x[i:i + 1], *args, **kw)
                            for i in range(x.shape[0])])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(loop))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_conv2d_batched_vs_looped(dtype):
    x = rnd((N, H, W, 4), dtype)
    w = rnd((3, 3, 4, 8), dtype, jax.random.PRNGKey(1))
    kw = dict(requant_shift=5) if dtype == jnp.int8 else {}
    _assert_batched_equals_looped(conv2d_im2col, x, w,
                                  cfg={**TILED_CFG, "block_co": 4}, **kw)


def test_conv2d_grouped_batched_vs_looped():
    x = rnd((N, H, W, 6), jnp.int8)
    w = rnd((3, 3, 2, 9), jnp.int8, jax.random.PRNGKey(1))
    _assert_batched_equals_looped(conv2d_im2col, x, w,
                                  cfg={**TILED_CFG, "block_co": 3},
                                  groups=3, requant_shift=4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_depthwise_batched_vs_looped(dtype):
    x = rnd((N, H, W, 8), dtype)
    w = rnd((3, 3, 8), dtype, jax.random.PRNGKey(1))
    kw = dict(requant_shift=4) if dtype == jnp.int8 else {}
    _assert_batched_equals_looped(depthwise2d, x, w,
                                  cfg={**TILED_CFG, "block_c": 4}, **kw)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_shift_batched_vs_looped(dtype):
    c, cy = 6, 8
    x = rnd((N, H, W, c), dtype)
    shifts = np.array([[(i % 3) - 1, ((i * 2) % 3) - 1] for i in range(c)],
                      np.int32)
    w = rnd((c, cy), dtype, jax.random.PRNGKey(1))
    kw = dict(requant_shift=5) if dtype == jnp.int8 else {}
    _assert_batched_equals_looped(shift_conv2d, x, shifts, w,
                                  cfg={**TILED_CFG, "block_co": 4}, **kw)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_add_batched_vs_looped(dtype):
    x = rnd((N, H, W, 4), dtype)
    w = rnd((3, 3, 4, 6), dtype, jax.random.PRNGKey(1))
    kw = dict(requant_shift=3, w_preshift=1) if dtype == jnp.int8 else {}
    _assert_batched_equals_looped(add_conv2d, x, w,
                                  cfg={**TILED_CFG, "block_co": 2}, **kw)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_pool_batched_vs_looped(dtype):
    x = rnd((N, 11, 9, 8), dtype)
    got = maxpool2d(x, window=3, stride=2,
                    config={**TILED_CFG, "block_h": 2, "block_w": 2,
                            "block_c": 4})
    loop = jnp.concatenate([maxpool2d(x[i:i + 1], window=3, stride=2)
                            for i in range(N)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(loop))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_matmul_batched_vs_looped(dtype):
    a = rnd((3, 16, 24), dtype)
    b = rnd((24, 8), dtype, jax.random.PRNGKey(1))
    kw = dict(requant_shift=5) if dtype == jnp.int8 else {}
    got = matmul(a, b, bm=16, bn=8, bk=16, **kw)
    loop = jnp.stack([matmul(a[i], b, bm=16, bn=8, bk=16, **kw)
                      for i in range(3)])
    assert got.shape == (3, 16, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(loop))


def test_tiled_schedule_with_fused_epilogue_matches_oracle():
    """bias + relu + requantization epilogues survive the tiled grid."""
    from repro.kernels import ref
    x = rnd((3, 10, 10, 8), jnp.int8)
    w = rnd((3, 3, 8, 16), jnp.int8, jax.random.PRNGKey(1))
    b = jnp.arange(16, dtype=jnp.int32) * 50
    got = conv2d_im2col(x, w, bias=b, requant_shift=5, act="relu",
                        config={"block_n": 3, "block_h": 4, "block_w": 8,
                                "block_co": 8})
    want = ref.conv2d_q8_ref(x, w, b, requant_shift=5, act="relu")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------- W4 weights x tiled schedules --
#
# Packed weights are grid-invariant (only activations are spatially tiled),
# but the ragged final halo tiles and non-pow2 batch blocks exercise the
# in-register unpack against partial blocks — W4 under the tiled schedule
# must stay bit-exact with the unpacked-int8 oracle AND the per-image loop.

def _w4(w, axis, group=4):
    from repro.core.quantize import quantize_w4
    qt = quantize_w4(w, axis=axis, group_size=group)
    return qt.q, qt.shifts, qt.expand()


def test_conv2d_w4_tiled_vs_oracle_and_looped():
    from repro.kernels import ref
    x = rnd((N, H, W, 5), jnp.int8)                  # odd Cx: pad nibble
    wp, ws, w8 = _w4(rnd((3, 3, 5, 8), key=jax.random.PRNGKey(1)), 2)
    kw = dict(requant_shift=5, w_shifts=ws)
    _assert_batched_equals_looped(conv2d_im2col, x, wp,
                                  cfg={**TILED_CFG, "block_co": 4}, **kw)
    got = conv2d_im2col(x, wp, config={**TILED_CFG, "block_co": 4}, **kw)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.conv2d_q8_ref(x, w8, requant_shift=5)))


def test_depthwise_w4_tiled_vs_oracle_and_looped():
    from repro.kernels import ref
    x = rnd((N, H, W, 8), jnp.int8)
    wp, ws, w8 = _w4(rnd((3, 3, 8), key=jax.random.PRNGKey(1)), 0, group=2)
    kw = dict(requant_shift=4, w_shifts=ws)
    _assert_batched_equals_looped(depthwise2d, x, wp,
                                  cfg={**TILED_CFG, "block_c": 4}, **kw)
    got = depthwise2d(x, wp, config={**TILED_CFG, "block_c": 4}, **kw)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.depthwise2d_q8_ref(x, w8, requant_shift=4)))


def test_shift_w4_tiled_vs_oracle_and_looped():
    from repro.kernels import ref
    c, cy = 6, 8
    x = rnd((N, H, W, c), jnp.int8)
    shifts = np.array([[(i % 3) - 1, ((i * 2) % 3) - 1] for i in range(c)],
                      np.int32)
    wp, ws, w8 = _w4(rnd((c, cy), key=jax.random.PRNGKey(1)), 0, group=2)
    kw = dict(requant_shift=5, w_shifts=ws)
    _assert_batched_equals_looped(shift_conv2d, x, shifts, wp,
                                  cfg={**TILED_CFG, "block_co": 4}, **kw)
    got = shift_conv2d(x, shifts, wp,
                       config={**TILED_CFG, "block_co": 4}, **kw)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.shift_conv2d_q8_ref(x, shifts, w8, requant_shift=5)))


def test_add_w4_tiled_vs_oracle_and_looped():
    from repro.kernels import ref
    x = rnd((N, H, W, 4), jnp.int8)
    wp, ws, w8 = _w4(rnd((3, 3, 4, 6), key=jax.random.PRNGKey(1)), 2)
    kw = dict(requant_shift=3, w_preshift=1, w_shifts=ws)
    _assert_batched_equals_looped(add_conv2d, x, wp,
                                  cfg={**TILED_CFG, "block_co": 2}, **kw)
    got = add_conv2d(x, wp, config={**TILED_CFG, "block_co": 2}, **kw)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.add_conv2d_q8_ref(x, w8, requant_shift=3,
                                         w_preshift=1)))


def test_matmul_w4_batched_vs_looped():
    from repro.kernels import ref
    a = rnd((N, 16, 17), jnp.int8)                  # odd K: packed pad byte
    wp, ws, w8 = _w4(rnd((17, 8), key=jax.random.PRNGKey(1)), 0, group=4)
    kw = dict(requant_shift=5, w_shifts=ws)
    got = matmul(a, wp, bm=16, bn=8, bk=7, **kw)    # odd bk rounds even
    loop = jnp.stack([matmul(a[i], wp, bm=16, bn=8, bk=7, **kw)
                      for i in range(N)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(loop))
    np.testing.assert_array_equal(
        np.asarray(got[0]),
        np.asarray(ref.matmul_ref(a[0], w8, requant_shift=5)))


def test_plan_jobs_emit_w4_dtype():
    """A W4-lowered plan's tune jobs carry the "w4a8" dtype key (own cache
    signature + halved-weight-byte cost ranking) and the packed weights'
    group shifts, so the timed calls are the real W4 dispatches."""
    from repro.core.quantize import QTensorW4
    cfg = CNNConfig(primitive="standard", widths=(8, 12), image_size=16)
    params = init_cnn(cfg, jax.random.PRNGKey(1))
    calib = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 16, 3)) * 0.5
    plan = lower(build_cnn_graph(cfg), params, calib, weight_bits=4,
                 group_size=8)
    jobs = tune.plan_jobs(plan, batch=2)
    w4_jobs = [j for j in jobs if j[3] == "w4a8"]
    assert w4_jobs, "W4 plan produced no w4a8 tune jobs"
    for kernel, sig, arrays, dtype, kwargs in w4_jobs:
        assert "w_shifts" in kwargs


def test_ops_dispatch_accepts_tiled_configs():
    """The ops layer threads the new knobs through config= like any other
    schedule parameter (pallas == xla on a tiled schedule)."""
    x = rnd((4, 12, 12, 8))
    w = rnd((3, 3, 8, 256), key=jax.random.PRNGKey(1))
    got = ops.conv2d(x, w, config={"block_n": 2, "block_h": 8,
                                   "block_co": 128})
    want = ops.conv2d(x, w, method="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------- executor forward_batch --

def _lowered(prim, image=16):
    cfg = CNNConfig(primitive=prim, widths=(8, 12), image_size=image)
    params = init_cnn(cfg, jax.random.PRNGKey(1))
    calib = jax.random.normal(jax.random.PRNGKey(2),
                              (4, image, image, 3)) * 0.5
    return cfg, lower(build_cnn_graph(cfg), params, calib)


@pytest.mark.parametrize("prim", Primitives)
def test_forward_batch_matches_per_sample_loop(prim):
    """Acceptance: forward_batch(x[N]) == the per-sample loop. The integer
    trunk is bit-exact per node; the final logits (float gap->dense head)
    agree to tight tolerance and exactly by argmax (XLA picks batch-size-
    dependent float matmul kernels for the head)."""
    cfg, plan = _lowered(prim)
    x = jax.random.normal(jax.random.PRNGKey(3), (N, 16, 16, 3)) * 0.5
    ex = CompiledPlan(plan, method="xla")
    got = ex.forward_batch(x)
    loop = jnp.concatenate([ex(x[i:i + 1]) for i in range(N)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(loop),
                               rtol=1e-5, atol=1e-6)
    assert (np.asarray(got).argmax(-1) == np.asarray(loop).argmax(-1)).all()
    # integer trunk: bitwise, batched vs looped, at every plan node
    exn = CompiledPlan(plan, method="xla", jit=False)
    h = quantize(x, plan.in_fb)
    hl = [quantize(x[i:i + 1], plan.in_fb) for i in range(N)]
    for node in plan.nodes:
        h = exn._run_node(node, h)
        hl = [exn._run_node(node, v) for v in hl]
        if isinstance(h, QTensor):
            np.testing.assert_array_equal(
                np.asarray(h.q),
                np.asarray(jnp.concatenate([v.q for v in hl])), err_msg=node.name)


def test_forward_batch_pallas_matches_xla():
    cfg, plan = _lowered("dws")
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 16, 16, 3)) * 0.5
    got = CompiledPlan(plan, method="pallas").forward_batch(x)
    want = CompiledPlan(plan, method="xla").forward_batch(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_batch_compiles_once_per_bucket():
    """Acceptance: pow2 batch bucketing bounds recompiles — every batch
    size inside a bucket reuses the bucket's single trace."""
    cfg, plan = _lowered("standard")
    ex = CompiledPlan(plan, method="xla")
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 16, 16, 3)) * 0.5
    assert CompiledPlan.batch_bucket(5) == 8
    assert CompiledPlan.batch_bucket(8) == 8
    assert CompiledPlan.batch_bucket(9) == 16
    for n in (5, 6, 7, 8):               # one bucket -> one trace
        ex.forward_batch(x[:n])
    assert ex.traces == 1
    ex.forward_batch(x[:3])              # bucket 4 -> exactly one more
    assert ex.traces == 2
    ex.forward_batch(x[:2])
    assert ex.traces == 3 and ex.forward_batch(x[:1]).shape[0] == 1


def test_throughput_and_profile_mode():
    cfg, plan = _lowered("standard")
    ex = CompiledPlan(plan, method="xla")
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 16, 16, 3)) * 0.5
    tp = ex.throughput(x, reps=1, warmup=1)
    assert tp["batch"] == 4 and tp["bucket"] == 4
    assert tp["images_per_s"] > 0 and tp["us_per_image"] == tp["us_per_batch"] / 4
    rows = ex.profile(x, reps=1, mode="throughput")
    assert rows and all(r["images_per_s"] > 0 for r in rows)
    with pytest.raises(ValueError, match="mode"):
        ex.profile(x, mode="bogus")


# --------------------------------------------------------- tune v2 space ---

def test_space_carries_tiled_knobs():
    sig = tune.sig_conv2d(8, 32, 32, 16, 32, 3)
    cands = list(tune.candidates(sig, "int8"))
    assert any(c.get("block_n", 1) > 1 for c in cands)
    assert any("block_h" in c for c in cands)
    assert tune.default_config("conv2d") in cands
    # effective resolution goes through the kernels' own schedule helper
    eff = tune.effective_config(sig, {"block_n": 8, "block_h": 8})
    assert eff["block_n"] == 8 and eff["block_h"] == 8 and eff["block_w"] == 32
    # infeasible block_n degrades like the kernel grid does
    eff = tune.effective_config(tune.sig_conv2d(5, 9, 7, 4, 8, 3),
                                {"block_n": 4, "block_h": 4})
    assert eff["block_n"] == 1 and eff["block_h"] == 4 and eff["block_w"] == 7


def test_maxpool_is_tunable_and_parity_with_planted_config():
    sig = tune.sig_maxpool2d(4, 12, 12, 8, 2, 2)
    cands = list(tune.candidates(sig, "int8"))
    assert tune.default_config("maxpool2d") in cands and len(cands) > 1
    key = tune.cache_key("maxpool2d", sig.key(), "int8", tune.backend_tag())
    c = tune.TuneCache(None)
    c.put(key, {"block_c": 4, "block_n": 2, "block_h": 3}, us=1.0)
    tune.set_default_cache(c)
    x = rnd((4, 12, 12, 8), jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(ops.maxpool2d(x, method="pallas")),
        np.asarray(ops.maxpool2d(x, method="xla")))


def test_analytic_fallback_feasible_on_batched_shapes():
    for sig in [tune.sig_conv2d(8, 32, 32, 16, 32, 3),
                tune.sig_add_conv2d(8, 10, 10, 8, 8, 3),
                tune.sig_maxpool2d(8, 32, 32, 16, 2, 2)]:
        cfg = tune.analytic_config(sig, "int8")
        assert cfg in list(tune.candidates(sig, "int8"))
        assert tune.estimate_s(sig, cfg, "int8") > 0


def test_schema_v3_rejects_v1_cache(tmp_path):
    """Schema bumps (v2: tiled knobs; v3: the W4A8 "w4a8" dtype key + its
    halved-weight-traffic reranking) must make old caches be ignored
    wholesale, not misapplied."""
    assert tune.SCHEMA_VERSION == 3
    path = str(tmp_path / "v1.json")
    key = tune.cache_key("conv2d", "n1_h8_w8_ci4_co8_k3_g1", "float32",
                         tune.backend_tag())
    json.dump({"schema_version": 1,
               "entries": {key: {"config": {"block_co": 1}, "us": 1.0,
                                 "source": "measured"}}}, open(path, "w"))
    c = tune.TuneCache(path)
    assert c.stale and len(c) == 0


def test_plan_jobs_cover_maxpool_at_serving_batch():
    cfg, plan = _lowered("standard")
    jobs = tune.plan_jobs(plan, batch=8)
    kinds = {j[0] for j in jobs}
    assert "maxpool2d" in kinds and "conv2d" in kinds
    for kernel, sig, arrays, dtype, kwargs in jobs:
        assert sig.get("n") == 8 and arrays[0].shape[0] == 8


# ----------------------------------------------------- CNN serving engine --

def test_cnn_engine_serves_queued_requests():
    from repro.serve import CNNEngine, CNNServeConfig, ImageRequest
    cfg, plan = _lowered("standard")
    ex = CompiledPlan(plan, method="xla")
    eng = CNNEngine(ex, CNNServeConfig(max_batch=4))
    rng = np.random.default_rng(0)
    imgs = [rng.normal(size=(16, 16, 3)).astype(np.float32) * 0.5
            for _ in range(11)]          # 3 rounds: 4 + 4 + ragged 3
    for uid, img in enumerate(imgs):
        eng.submit(ImageRequest(uid, img))
    done = eng.run_until_drained()
    # ragged last round (3 images) reused the pow2 bucket of the full rounds
    assert ex.traces == 1
    assert len(done) == 11 and all(r.done for r in done)
    s = eng.stats
    assert s["batch_rounds"] == 3 and s["images_done"] == 11
    assert 0 < s["occupancy"] <= 1 and s["images_per_s"] > 0
    # logits match the direct batched forward, request by request
    want = np.asarray(ex.forward_batch(np.stack(imgs)))
    by_uid = {r.uid: r.logits for r in done}
    for uid in range(11):
        np.testing.assert_allclose(by_uid[uid], want[uid],
                                   rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="max_batch"):
        CNNEngine(ex, CNNServeConfig(max_batch=0))


# ------------------------------------------------ interpret default flip ---

def test_interpret_default_is_backend_detected(monkeypatch):
    from repro.kernels.common import resolve_interpret, use_interpret
    assert resolve_interpret(None) == use_interpret()
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")   # the CI pin
    assert resolve_interpret(None) is True
