"""repro.tune subsystem tests: search-space feasibility, analytic fallback,
cache roundtrip + schema versioning, dispatch integration, and ops-level
Pallas-vs-XLA parity across all five primitives (the dispatch layer resolves
schedules through the tuner, so parity here exercises the whole stack)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.kernels import ops
from repro.tune import cache as tcache

KEY = jax.random.PRNGKey(0)


def rnd(shape, dtype=jnp.float32, key=KEY, scale=1.0):
    if jnp.issubdtype(dtype, jnp.integer):
        return jax.random.randint(key, shape, -100, 100, jnp.int32).astype(dtype)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.fixture(autouse=True)
def _clean_tuner_state():
    """Each test starts from no persistent cache and an empty memo."""
    tune.set_default_cache(tune.TuneCache(None))
    yield
    tune.reset()


# ------------------------------------------------------------- space ------

ALL_SIGS = [
    tune.sig_conv2d(1, 8, 8, 4, 8, 3),
    tune.sig_conv2d(2, 12, 12, 16, 16, 5, 4),
    tune.sig_depthwise2d(1, 8, 8, 12, 3),
    tune.sig_shift_conv2d(1, 8, 8, 8, 12),
    tune.sig_add_conv2d(1, 6, 6, 4, 6, 3),
    tune.sig_causal_conv1d(2, 96, 48, 4),
    tune.sig_matmul(96, 64, 80),
]


@pytest.mark.parametrize("sig", ALL_SIGS, ids=lambda s: s.kernel + "/" + s.key())
def test_space_contains_default_and_is_finite(sig):
    cands = list(tune.candidates(sig))
    assert 1 <= len(cands) <= 64
    assert tune.default_config(sig.kernel) in cands
    # no duplicate configs
    keys = [tuple(sorted(c.items())) for c in cands]
    assert len(keys) == len(set(keys))


@pytest.mark.parametrize("sig", ALL_SIGS, ids=lambda s: s.kernel + "/" + s.key())
def test_analytic_fallback_is_feasible(sig):
    cfg = tune.analytic_config(sig, "float32")
    assert cfg in list(tune.candidates(sig))
    assert tune.estimate_s(sig, cfg, "float32") > 0


def test_unknown_kernel_rejected():
    with pytest.raises(ValueError):
        tune.default_config("bogus")
    with pytest.raises(ValueError):
        tune.ShapeSig("bogus", (("m", 8),))


# ------------------------------------------------------------- cache ------

def test_cache_roundtrip(tmp_path):
    path = str(tmp_path / "tuned.json")
    c = tune.TuneCache(None)
    key = tune.cache_key("matmul", "m64_k64_n64", "float32", "cpu+interpret")
    c.put(key, {"bm": 64, "bn": 64, "bk": 64}, us=12.5, source="measured")
    c.save(path)

    c2 = tune.TuneCache(path)
    assert not c2.stale
    entry = c2.get(key)
    assert entry["config"] == {"bm": 64, "bn": 64, "bk": 64}
    assert entry["us"] == 12.5
    assert entry["source"] == "measured"
    blob = json.load(open(path))
    assert blob["schema_version"] == tune.SCHEMA_VERSION


def test_cache_schema_version_mismatch(tmp_path):
    path = str(tmp_path / "stale.json")
    key = tune.cache_key("matmul", "m64_k64_n64", "float32", "cpu+interpret")
    blob = {"schema_version": tune.SCHEMA_VERSION + 1,
            "entries": {key: {"config": {"bm": 1}, "us": 1.0,
                              "source": "measured"}}}
    json.dump(blob, open(path, "w"))

    c = tune.TuneCache(path)
    assert c.stale
    assert len(c) == 0 and c.get(key) is None   # never misapply stale configs

    # dispatch falls back to the analytic schedule, not the stale entry
    tune.set_default_cache(c)
    cfg = tune.get_config(tune.sig_matmul(64, 64, 64), "float32")
    assert cfg != {"bm": 1}
    assert cfg in list(tune.candidates(tune.sig_matmul(64, 64, 64)))


def test_cache_corrupt_file_ignored(tmp_path):
    path = str(tmp_path / "corrupt.json")
    open(path, "w").write("{not json")
    c = tune.TuneCache(path)
    assert c.stale and len(c) == 0


def test_get_config_prefers_cache_then_memoizes():
    sig = tune.sig_matmul(64, 64, 64)
    tagged = tune.cache_key("matmul", sig.key(), "float32", tune.backend_tag())
    c = tune.TuneCache(None)
    c.put(tagged, {"bm": 32, "bn": 32, "bk": 32}, us=1.0)
    tune.set_default_cache(c)
    assert tune.get_config(sig, "float32") == {"bm": 32, "bn": 32, "bk": 32}
    # memo survives swapping the cache out (in-process memoization)
    tcache._default_cache = tune.TuneCache(None)
    assert tune.get_config(sig, "float32") == {"bm": 32, "bn": 32, "bk": 32}


def test_get_config_analytic_when_no_cache():
    sig = tune.sig_conv2d(1, 8, 8, 8, 16, 3)
    cfg = tune.get_config(sig, "float32")
    assert cfg in list(tune.candidates(sig))


# ---------------------------------------------------------- autotune ------

def test_autotune_records_best_and_default(tmp_path):
    a = rnd((32, 32))
    sig = tune.sig_matmul(32, 32, 32)
    c = tune.TuneCache(None)
    best, best_us = tune.autotune_into(c, "matmul", sig, (a, a), "float32",
                                       reps=1, warmup=1, max_candidates=3)
    key = tune.cache_key("matmul", sig.key(), "float32", tune.backend_tag())
    entry = c.get(key)
    assert entry["config"] == best and entry["source"] == "measured"
    assert entry["us"] == best_us > 0
    path = str(tmp_path / "t.json")
    c.save(path)
    tune.set_default_cache(tune.TuneCache(path))
    assert tune.get_config(sig, "float32") == best


# ------------------------------------- ops-level Pallas-vs-XLA parity -----
# The Pallas side resolves its schedule through the tuner (analytic
# fallback, then a planted cache entry) — parity across primitives, shapes
# and dtypes is the end-to-end guarantee the dispatch integration needs.

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    # (N, H, W, Cx, Cy, HK, groups)
    (1, 8, 8, 4, 8, 3, 1),
    (2, 10, 10, 8, 12, 5, 1),
    (1, 9, 9, 6, 9, 3, 3),
])
def test_ops_conv2d_parity(shape, dtype):
    n, h, w, cx, cy, hk, g = shape
    x = rnd((n, h, w, cx), dtype)
    wt = rnd((hk, hk, cx // g, cy), dtype, jax.random.PRNGKey(1))
    got = ops.conv2d(x, wt, groups=g)
    want = ops.conv2d(x, wt, groups=g, method="xla")
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h,w,c,hk", [(1, 8, 8, 8, 3), (2, 10, 6, 16, 5)])
def test_ops_depthwise_parity(n, h, w, c, hk, dtype):
    x = rnd((n, h, w, c), dtype)
    wd = rnd((hk, hk, c), dtype, jax.random.PRNGKey(1))
    got = ops.depthwise2d(x, wd)
    want = ops.depthwise2d(x, wd, method="xla")
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h,w,c,cy", [(1, 8, 8, 9, 8), (2, 6, 10, 18, 12)])
def test_ops_shift_parity(n, h, w, c, cy, dtype):
    x = rnd((n, h, w, c), dtype)
    shifts = jnp.array([[(i % 3) - 1, ((i // 3) % 3) - 1] for i in range(c)],
                       jnp.int32)
    wp = rnd((c, cy), dtype, jax.random.PRNGKey(1))
    got = ops.shift_conv2d(x, shifts, wp)
    want = ops.shift_conv2d(x, shifts, wp, method="xla")
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h,w,cx,cy,hk", [(1, 6, 6, 4, 6, 3),
                                            (1, 8, 8, 3, 4, 5)])
def test_ops_add_parity(n, h, w, cx, cy, hk, dtype):
    x = rnd((n, h, w, cx), dtype)
    wt = rnd((hk, hk, cx, cy), dtype, jax.random.PRNGKey(1))
    got = ops.add_conv2d(x, wt)
    want = ops.add_conv2d(x, wt, method="xla")
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,l,d,k", [(1, 32, 16, 4), (2, 48, 24, 3)])
def test_ops_causal_conv1d_parity(b, l, d, k, dtype):
    x = rnd((b, l, d), dtype)
    w = rnd((k, d), dtype, jax.random.PRNGKey(1))
    got = ops.causal_conv1d(x, w, method="pallas")
    want = ops.causal_conv1d(x, w, method="xla")
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_ops_matmul_parity(dtype):
    a = rnd((48, 40), dtype)
    b = rnd((40, 56), dtype, jax.random.PRNGKey(1))
    shift = 6 if dtype == jnp.int8 else None
    got = ops.matmul(a, b, requant_shift=shift)
    want = ops.matmul(a, b, requant_shift=shift, method="xla")
    if dtype == jnp.int8:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, **tol(dtype))


def test_ops_parity_with_planted_cache_config():
    """A cache entry with a non-default (still feasible) schedule must not
    change results, only the schedule."""
    sig = tune.sig_conv2d(1, 8, 8, 8, 16, 3)
    key = tune.cache_key("conv2d", sig.key(), "float32", tune.backend_tag())
    c = tune.TuneCache(None)
    c.put(key, {"block_co": 4}, us=1.0)
    tune.set_default_cache(c)
    x = rnd((1, 8, 8, 8))
    w = rnd((3, 3, 8, 16), key=jax.random.PRNGKey(1))
    np.testing.assert_allclose(ops.conv2d(x, w),
                               ops.conv2d(x, w, method="xla"),
                               rtol=2e-5, atol=2e-5)


def test_ops_explicit_config_overrides():
    x = rnd((1, 8, 8, 8))
    w = rnd((3, 3, 8, 256), key=jax.random.PRNGKey(1))
    np.testing.assert_allclose(ops.conv2d(x, w, config={"block_co": 128}),
                               ops.conv2d(x, w, method="xla"),
                               rtol=2e-5, atol=2e-5)
