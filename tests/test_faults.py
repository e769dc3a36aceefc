"""repro.faults: deterministic injection core, the hardened serving layer,
and the chaos invariants (EXPERIMENTS.md §Resilience).

Structure mirrors the failure model:
  * injection core — FaultSpec validation, nth/times windows, seeded
    probability determinism, env grammar, nesting, corrupt determinism;
  * BlockPool integrity — double-free / unknown-page / stale-acquire
    ValueErrors, plus a seeded randomized op-sequence sweep auditing the
    pool's structural invariants after every operation;
  * crash-safe tune cache — truncated / non-object JSON warns and falls
    back instead of raising out of construction;
  * kernel degradation — sticky per-kernel pallas->xla fallback behind
    the ``kernels.dispatch`` seam, bit-exact with the oracle;
  * LM/CNN chaos — paired clean/faulted drains through
    ``repro.faults.chaos``: survivors bit-identical, every request
    terminal, pool conserved, spans balanced.

The CI chaos job reruns this file under a REPRO_FAULTS_SEED matrix; the
seeded tests read that env var so each matrix leg exercises a different
deterministic schedule against the same invariants.
"""
import dataclasses
import json
import os
import warnings

import jax
import numpy as np
import pytest

from repro import tune
from repro.configs import get_config
from repro.faults import FaultPlan, FaultSpec, InjectedFault, inject
from repro.faults import chaos
from repro.models import api
from repro.serve import Engine, QueueFullError, Request, ServeConfig
from repro.serve.engine import BlockPool

# the CI chaos matrix pins this; locally it defaults to 0
MATRIX_SEED = int(os.environ.get("REPRO_FAULTS_SEED", "0"))


# ------------------------------------------------------------ injection core


def test_spec_validation():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec(site="engine.nope", kind="raise")
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(site="engine.prefill", kind="explode")
    with pytest.raises(ValueError, match="corrupt"):
        FaultSpec(site="blockpool.alloc", kind="corrupt")
    with pytest.raises(ValueError, match="exactly one"):
        FaultSpec(site="engine.prefill", kind="raise", nth=1,
                  probability=0.5)
    with pytest.raises(ValueError, match="nth"):
        FaultSpec(site="engine.prefill", kind="raise", nth=0)
    with pytest.raises(ValueError, match="probability"):
        FaultSpec(site="engine.prefill", kind="raise", probability=1.5)
    with pytest.raises(ValueError, match="times"):
        FaultSpec(site="engine.prefill", kind="raise", times=0)
    with pytest.raises(ValueError, match="delay_s"):
        FaultSpec(site="engine.prefill", kind="delay", delay_s=-1)
    # nth defaults to 1 when neither trigger is given
    assert FaultSpec(site="engine.prefill", kind="raise").nth == 1


def test_nth_window_fires_consecutively():
    plan = FaultPlan([FaultSpec(site="engine.prefill", kind="raise",
                                nth=3, times=2)])
    fired_hits = []
    with plan:
        for h in range(1, 8):
            try:
                inject.check("engine.prefill")
            except InjectedFault:
                fired_hits.append(h)
    assert fired_hits == [3, 4]
    assert len(plan.log) == 2 and [f.hit for f in plan.log] == [3, 4]
    # other sites' counters are independent
    with plan:
        assert inject.check("engine.decode_round") is None


def test_inactive_check_is_none_and_counts_nothing():
    assert inject.active_plan() is None
    assert inject.check("engine.prefill") is None


def test_probability_is_seed_deterministic():
    def fires(seed):
        plan = FaultPlan([FaultSpec(site="engine.decode_round",
                                    kind="raise", probability=0.4,
                                    times=100)], seed=seed)
        out = []
        with plan:
            for h in range(1, 51):
                try:
                    inject.check("engine.decode_round")
                except InjectedFault:
                    out.append(h)
        return out

    a, b = fires(MATRIX_SEED), fires(MATRIX_SEED)
    assert a == b and 0 < len(a) < 50
    assert fires(MATRIX_SEED + 1) != a


def test_reset_restores_counters():
    plan = FaultPlan([FaultSpec(site="engine.prefill", kind="raise",
                                nth=1)])
    with plan:
        with pytest.raises(InjectedFault):
            inject.check("engine.prefill")
        assert inject.check("engine.prefill") is None   # window passed
    plan.reset()
    with plan:
        with pytest.raises(InjectedFault):               # fires again
            inject.check("engine.prefill")


def test_nesting_restores_previous_plan():
    outer = FaultPlan([FaultSpec(site="engine.prefill", kind="raise",
                                 nth=10**9)])
    inner = FaultPlan([])
    with outer:
        assert inject.active_plan() is outer
        with inner:
            assert inject.active_plan() is inner
        assert inject.active_plan() is outer
    assert inject.active_plan() is None


def test_env_grammar():
    plan = inject.parse_env(
        "engine.decode_round:raise:nth=2:times=3;"
        "kernels.dispatch:delay:p=0.25:delay=0.01; seed=41")
    assert plan.seed == 41 and len(plan.specs) == 2
    a, b = plan.specs
    assert (a.site, a.kind, a.nth, a.times) == \
        ("engine.decode_round", "raise", 2, 3)
    assert (b.site, b.kind, b.probability, b.delay_s) == \
        ("kernels.dispatch", "delay", 0.25, 0.01)
    with pytest.raises(ValueError, match="site:kind"):
        inject.parse_env("engine.decode_round")
    with pytest.raises(ValueError, match="malformed"):
        inject.parse_env("engine.decode_round:raise:nth")
    with pytest.raises(ValueError, match="unknown"):
        inject.parse_env("engine.decode_round:raise:bogus=1")
    with pytest.raises(ValueError, match="unknown fault site"):
        inject.parse_env("engine.bogus:raise")


def test_corrupt_apply_is_deterministic_and_out_of_band():
    f = inject.Fired(site="engine.decode_round", kind="corrupt", hit=3,
                     seed=MATRIX_SEED)
    x = np.linspace(-1.0, 1.0, 64, dtype=np.float32).reshape(4, 16)
    a, b = f.apply(x), f.apply(x)
    np.testing.assert_array_equal(a, b)          # deterministic
    assert a.shape == x.shape and not np.array_equal(a, x)
    assert a.max() > x.max() + 500               # out-of-band: moves argmax
    # a different hit corrupts different positions/values
    g = inject.Fired(site="engine.decode_round", kind="corrupt", hit=4,
                     seed=MATRIX_SEED)
    assert not np.array_equal(g.apply(x), a)
    # integer arrays poison to dtype max
    xi = np.zeros((8,), np.int32)
    assert f.apply(xi).max() == np.iinfo(np.int32).max


def test_delay_kind_sleeps_and_returns_none():
    import time
    plan = FaultPlan([FaultSpec(site="engine.prefill", kind="delay",
                                nth=1, delay_s=0.05)])
    with plan:
        t0 = time.perf_counter()
        assert inject.check("engine.prefill") is None
        assert time.perf_counter() - t0 >= 0.04
    assert len(plan.log) == 1


# --------------------------------------------------------- BlockPool safety


def test_pool_double_free_raises():
    pool = BlockPool(8, 4)
    ids = pool.alloc(2)
    pool.free(ids)
    with pytest.raises(ValueError, match="double-free or unknown"):
        pool.free(ids)
    with pytest.raises(ValueError, match="double-free or unknown"):
        pool.free([999])
    assert pool.audit(expect_drained=True) == []


def test_pool_release_without_reference_raises():
    pool = BlockPool(8, 4)
    with pytest.raises(ValueError, match="no live reference"):
        pool.release([3])
    ids = pool.alloc(1)
    pool.publish(["d0"], ids)
    pool.release(ids)
    with pytest.raises(ValueError, match="no live reference"):
        pool.release(ids)                        # double-release
    assert pool.audit(expect_drained=True) == []


def test_pool_free_of_referenced_or_published_page_raises():
    pool = BlockPool(8, 4)
    ids = pool.alloc(2)
    pool.publish(["d0"], ids[:1])
    with pytest.raises(ValueError, match="live"):
        pool.free(ids[:1])                       # has a live reference
    pool.release(ids[:1])                        # parks it evictable
    with pytest.raises(ValueError, match="published/parked"):
        pool.free(ids[:1])                       # parked pages use hashed=
    pool.free(ids[1:])
    assert pool.audit() == []


def test_pool_acquire_revalidates_evicted_page():
    pool = BlockPool(4, 4)                       # 3 usable pages
    ids = pool.alloc(1)
    pool.publish(["d0"], ids)
    pool.release(ids)                            # parked, evictable
    hit = pool.lookup(["d0"])
    assert hit == ids
    assert pool.alloc(3) is not None             # evicts the parked page
    with pytest.raises(ValueError, match="evicted"):
        pool.acquire(hit)                        # stale lookup result


def test_pool_randomized_op_sequence_keeps_invariants():
    """Property-style sweep: a seeded random interleaving of alloc /
    publish / acquire / release / free / lookup keeps every structural
    invariant (audit() == []) after EVERY op, and full teardown drains
    clean. The CI seed matrix varies the interleaving."""
    rng = np.random.default_rng(MATRIX_SEED)
    pool = BlockPool(10, 4)
    live = []          # [ids, hashed] per simulated request
    next_digest = 0
    for step in range(300):
        op = rng.integers(0, 4)
        if op == 0:                                        # admit
            n = int(rng.integers(1, 4))
            ids = pool.alloc(n)
            if ids is not None:
                h = int(rng.integers(0, n + 1))
                keys = [f"d{next_digest + j}" for j in range(h)]
                next_digest += h
                pool.publish(keys, ids[:h])
                live.append([ids, h, keys])
        elif op == 1 and live:                             # retire
            ids, h, _ = live.pop(int(rng.integers(0, len(live))))
            pool.free(ids, hashed=h)
        elif op == 2 and live:                             # share a prefix
            _, h, keys = live[int(rng.integers(0, len(live)))]
            if h:
                hit = pool.lookup(keys)
                if hit:                                    # may be evicted
                    pool.acquire(hit)
                    live.append([hit, len(hit), keys[:len(hit)]])
        else:                                              # illegal free
            with pytest.raises(ValueError):
                pool.free([999])
        assert pool.audit() == [], f"step {step} broke an invariant"
    for ids, h, _ in live:
        pool.free(ids, hashed=h)
    assert pool.audit(expect_drained=True) == []
    assert len(pool._free) + len(pool._evictable) == pool.usable


def test_pool_alloc_fault_seam_fires_before_state_change():
    pool = BlockPool(8, 4)
    with FaultPlan([FaultSpec(site="blockpool.alloc", kind="raise",
                              nth=1)]):
        with pytest.raises(InjectedFault):
            pool.alloc(2)
        assert pool.audit() == [] and pool.in_use == 0
        assert pool.alloc(2) is not None         # next call succeeds


# ------------------------------------------------------ crash-safe tunecache


def test_truncated_tune_cache_warns_and_falls_back(tmp_path):
    path = str(tmp_path / "cache.json")
    c = tune.TuneCache(None)
    c.put(tune.cache_key("conv2d", "sig", "float32", "cpu"),
          {"block_co": 8}, us=1.0)
    c.save(path)
    blob = open(path).read()
    open(path, "w").write(blob[:len(blob) // 2])   # external truncation
    with pytest.warns(RuntimeWarning, match="unreadable"):
        broken = tune.TuneCache(path)
    assert broken.stale and len(broken) == 0


def test_wrong_typed_tune_cache_warns_and_falls_back(tmp_path):
    path = str(tmp_path / "cache.json")
    json.dump(["not", "a", "dict"], open(path, "w"))
    with pytest.warns(RuntimeWarning, match="unreadable"):
        broken = tune.TuneCache(path)
    assert broken.stale and len(broken) == 0


def test_tune_cache_load_fault_seam(tmp_path):
    path = str(tmp_path / "cache.json")
    c = tune.TuneCache(None)
    c.put("k", {"block_co": 8})
    c.save(path)
    with FaultPlan([FaultSpec(site="tune.cache_load", kind="raise",
                              nth=1)]):
        with pytest.warns(RuntimeWarning, match="unreadable"):
            broken = tune.TuneCache(path)
        assert broken.stale and len(broken) == 0
    assert len(tune.TuneCache(path)) == 1        # file itself is fine


# --------------------------------------------------------- kernel fallback


def test_kernel_dispatch_degrades_sticky_and_bit_exact():
    from repro.kernels import ops
    import jax.numpy as jnp
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 6, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 4)) * 0.1
    b = jnp.zeros((4,))
    want = np.asarray(ops.conv2d(x, w, b, method="xla"))
    ops.reset_degraded()
    try:
        plan = FaultPlan([FaultSpec(site="kernels.dispatch", kind="raise",
                                    nth=1, times=10**6)])
        with plan, warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            got = np.asarray(ops.conv2d(x, w, b, method="pallas"))
            hits_after_first = plan._hits["kernels.dispatch"]
            # sticky: the second call short-circuits to xla WITHOUT
            # re-attempting the pallas dispatch (no new seam hits)
            got2 = np.asarray(ops.conv2d(x, w, b, method="pallas"))
            assert plan._hits["kernels.dispatch"] == hits_after_first
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got2, want)
        assert "conv2d" in ops.degraded()
        warned = [w_ for w_ in rec
                  if issubclass(w_.category, RuntimeWarning)
                  and "degraded" in str(w_.message)]
        assert len(warned) == 1                  # logged once, not per call
    finally:
        ops.reset_degraded()
    assert ops.degraded() == {}


def test_real_kernel_error_raises_instead_of_degrading(monkeypatch):
    """Only injected faults degrade: a real kernel or compile error raises
    out of ops.* and leaves no sticky degradation behind."""
    from repro.kernels import ops

    def broken(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(ops, "_conv_pallas", broken)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 6, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 4))
    ops.reset_degraded()
    with pytest.raises(RuntimeError, match="Mosaic"):
        ops.conv2d(x, w, method="pallas")
    assert ops.degraded() == {}


# ----------------------------------------------------------------- LM chaos


def tiny_cfg():
    return dataclasses.replace(get_config("qwen2-0.5b"), n_layers=2,
                               d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                               vocab=64)


@pytest.fixture(scope="module")
def lm_setup():
    cfg = tiny_cfg()
    return cfg, api.init_params(cfg, jax.random.PRNGKey(0))


def make_reqs(n=4, plen=5, max_new=6, **kw):
    def factory():
        out = []
        for uid in range(n):
            rng = np.random.default_rng(uid)
            out.append(Request(
                uid=uid,
                prompt=rng.integers(0, 64, (plen,)).astype(np.int32),
                max_new_tokens=max_new, **kw))
        return out
    return factory


def lm_chaos(lm_setup, fault_plan, scfg_kw=None, req_kw=None, **harness_kw):
    cfg, params = lm_setup
    scfg = ServeConfig(max_batch=2, max_len=32, **(scfg_kw or {}))
    return chaos.run_lm_chaos(
        lambda: Engine(cfg, params, scfg),
        make_reqs(**(req_kw or {})),
        fault_plan, **harness_kw)


def test_lm_prefill_fault_absorbed_by_retry(lm_setup):
    rep = lm_chaos(lm_setup, FaultPlan(
        [FaultSpec(site="engine.prefill", kind="raise", nth=2, times=1)],
        seed=MATRIX_SEED))
    assert rep.ok, rep.summary()
    assert all(s == "ok" for s in rep.statuses.values())
    assert rep.fired == 1 and rep.stats["retries"] >= 1


def test_lm_prefill_fault_exhausts_retries_to_error(lm_setup):
    # times > max_retries+1 on one admission: that request retires as
    # "error"; every other stream stays bit-identical to the clean run
    rep = lm_chaos(lm_setup, FaultPlan(
        [FaultSpec(site="engine.prefill", kind="raise", nth=1, times=3)],
        seed=MATRIX_SEED))
    assert rep.ok, rep.summary()
    assert sorted(rep.statuses.values()) == ["error", "ok", "ok", "ok"]
    assert rep.stats["errors"] == 1


def test_lm_decode_fault_retires_active_set_and_rebuilds(lm_setup):
    # 3 consecutive decode failures exhaust max_retries=2: the active set
    # retires as "error", the arena is rebuilt, and the queued remainder
    # is served bit-identically against the fresh cache
    rep = lm_chaos(lm_setup, FaultPlan(
        [FaultSpec(site="engine.decode_round", kind="raise", nth=2,
                   times=3)], seed=MATRIX_SEED),
        req_kw=dict(n=5))
    assert rep.ok, rep.summary()
    by = sorted(rep.statuses.values())
    assert by.count("error") == 2 and by.count("ok") == 3
    assert rep.stats["arena_rebuilds"] == 1
    assert rep.stats["requests_done"] == 5


def test_lm_corrupt_round_is_contained(lm_setup):
    rep = lm_chaos(lm_setup, FaultPlan(
        [FaultSpec(site="engine.decode_round", kind="corrupt", nth=2)],
        seed=MATRIX_SEED), req_kw=dict(n=5))
    assert rep.ok, rep.summary()
    assert all(s == "ok" for s in rep.statuses.values())
    # the poisoned round's active requests are recorded and excluded from
    # bit-identity; later admissions decode clean and must survive
    assert rep.poisoned and rep.survivors
    assert set(rep.survivors).isdisjoint(rep.poisoned)


def test_lm_deadline_cancels_at_round_boundary(lm_setup):
    # every decode round stalls 30ms against a 10ms budget: requests get
    # their first token (prefill) then cancel at the next round boundary
    rep = lm_chaos(lm_setup, FaultPlan(
        [FaultSpec(site="engine.decode_round", kind="delay", nth=1,
                   times=10**6, delay_s=0.03)], seed=MATRIX_SEED),
        scfg_kw=dict(deadline_s=0.01))
    assert all(s in ("ok", "timeout") for s in rep.statuses.values())
    assert rep.stats["timeouts"] >= 1
    # timeout retirement reclaimed KV: conservation violations would show
    assert not rep.pool_violations and rep.ok, rep.summary()


def test_lm_shedding_reject_and_drop(lm_setup):
    for policy in ("reject", "drop"):
        rep = lm_chaos(lm_setup, FaultPlan([], seed=MATRIX_SEED),
                       scfg_kw=dict(max_queue=3, shed_policy=policy),
                       req_kw=dict(n=6), expect_fired=False)
        assert rep.ok, rep.summary()
        by = sorted(rep.statuses.values())
        assert by == ["ok", "ok", "ok", "shed", "shed", "shed"]
        if policy == "drop":
            assert rep.stats["shed"] == 3


def test_lm_paged_pool_fault_backpressures_not_leaks(lm_setup):
    rep = lm_chaos(lm_setup, FaultPlan(
        [FaultSpec(site="blockpool.alloc", kind="raise", nth=2, times=2)],
        seed=MATRIX_SEED),
        scfg_kw=dict(kv_layout="paged", kv_block_size=4, prefill_bucket=8),
        req_kw=dict(n=5, max_new=8))
    assert rep.ok, rep.summary()
    assert all(s == "ok" for s in rep.statuses.values())
    assert rep.pool_violations == []


def test_lm_paged_decode_error_rebuilds_pool_clean(lm_setup):
    rep = lm_chaos(lm_setup, FaultPlan(
        [FaultSpec(site="engine.decode_round", kind="raise", nth=3,
                   times=3)], seed=MATRIX_SEED),
        scfg_kw=dict(kv_layout="paged", kv_block_size=4, prefill_bucket=8),
        req_kw=dict(n=5, max_new=8))
    assert rep.ok, rep.summary()
    assert "error" in rep.statuses.values()
    assert rep.stats["arena_rebuilds"] == 1
    assert rep.pool_violations == []


def test_lm_static_scheduler_faults(lm_setup):
    for spec in (FaultSpec(site="engine.prefill", kind="raise", nth=1,
                           times=1),
                 FaultSpec(site="engine.decode_round", kind="raise", nth=1,
                           times=3),
                 FaultSpec(site="engine.decode_round", kind="corrupt",
                           nth=2)):
        rep = lm_chaos(lm_setup, FaultPlan([spec], seed=MATRIX_SEED),
                       scfg_kw=dict(scheduler="static"))
        assert rep.ok, rep.summary()
        assert all(s in ("ok", "error") for s in rep.statuses.values())


def test_lm_seeded_probability_chaos_matrix(lm_setup):
    """The CI-matrix leg: a probabilistic schedule over both hot seams at
    the env-pinned seed. Whatever fires, every invariant must hold."""
    rep = lm_chaos(lm_setup, FaultPlan(
        [FaultSpec(site="engine.decode_round", kind="raise",
                   probability=0.2, times=2),
         FaultSpec(site="engine.prefill", kind="raise", probability=0.2,
                   times=2)], seed=MATRIX_SEED),
        req_kw=dict(n=6), expect_fired=False)
    assert rep.ok, rep.summary()
    assert all(s in ("ok", "error") for s in rep.statuses.values())


def test_lm_env_activation_end_to_end(lm_setup, monkeypatch):
    """REPRO_FAULTS= is how the bench/CI layers schedule faults: install
    from the env, run a drain, and the schedule must both fire and be
    fully absorbed."""
    cfg, params = lm_setup
    monkeypatch.setenv(inject.ENV_VAR,
                       "engine.decode_round:raise:nth=2:times=1;"
                       f"seed={MATRIX_SEED}")
    inject.install_from_env(force=True)
    try:
        plan = inject.active_plan()
        assert plan is not None and plan.seed == MATRIX_SEED
        eng = Engine(cfg, params, ServeConfig(max_batch=2, max_len=32))
        for r in make_reqs()():
            eng.submit(r)
        done = eng.run_until_drained()
        assert all(r.status == "ok" for r in done)
        assert len(plan.log) == 1 and eng.stats["retries"] >= 1
    finally:
        inject.deactivate()


# ---------------------------------------------------------------- CNN chaos


def cnn_setup():
    from repro.graph import CompiledPlan, build_cnn_graph, lower
    from repro.models.convnet import CNNConfig, init_cnn
    cfg = CNNConfig(primitive="standard", widths=(8, 12), image_size=16)
    params = init_cnn(cfg, jax.random.PRNGKey(1))
    calib = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 16, 3)) * 0.5
    plan = lower(build_cnn_graph(cfg), params, calib)
    return plan, CompiledPlan


def make_images(n=6):
    def factory():
        from repro.serve import ImageRequest
        rng = np.random.default_rng(0)
        return [ImageRequest(uid, rng.normal(size=(16, 16, 3))
                             .astype(np.float32) * 0.5)
                for uid in range(n)]
    return factory


def test_cnn_round_fault_absorbed_by_retry():
    from repro.serve import CNNEngine, CNNServeConfig
    plan, CompiledPlan = cnn_setup()
    rep = chaos.run_cnn_chaos(
        lambda: CNNEngine(CompiledPlan(plan, method="xla"),
                          CNNServeConfig(max_batch=4)),
        make_images(), FaultPlan(
            [FaultSpec(site="cnn.batch_round", kind="raise", nth=1,
                       times=2)], seed=MATRIX_SEED))
    assert rep.ok, rep.summary()
    assert all(s == "ok" for s in rep.statuses.values())
    assert rep.stats["retries"] >= 2 and rep.stats["degraded"] == 0


def test_cnn_exhausted_retries_degrade_then_serve():
    """times > max_retries+1: the round exhausts its retries, the plan
    degrades to the xla path ONE-SHOT, and the same round then succeeds —
    nothing retires as error. A later fresh engine on the same (degraded)
    plan keeps serving without re-degrading."""
    from repro.serve import CNNEngine, CNNServeConfig
    plan, CompiledPlan = cnn_setup()
    ex = CompiledPlan(plan, method="xla")
    rep = chaos.run_cnn_chaos(
        lambda: CNNEngine(ex, CNNServeConfig(max_batch=4)),
        make_images(), FaultPlan(
            [FaultSpec(site="cnn.batch_round", kind="raise", nth=1,
                       times=3)], seed=MATRIX_SEED))
    # NOTE make_engine is called twice (baseline first), so ex.degraded
    # flips during the faulted run only — baseline ran clean
    assert rep.ok, rep.summary()
    assert all(s == "ok" for s in rep.statuses.values())
    assert ex.degraded and rep.stats["degraded"] == 1


def test_cnn_real_plan_error_propagates():
    """A real (non-injected) failure of the batched forward raises out of
    CNNEngine: no retry, no degradation to the xla path."""
    from repro.serve import CNNEngine, CNNServeConfig
    plan, CompiledPlan = cnn_setup()
    ex = CompiledPlan(plan, method="xla")

    def broken(x):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    ex.forward_batch = broken
    eng = CNNEngine(ex, CNNServeConfig(max_batch=4))
    for r in make_images(3)():
        eng.submit(r)
    with pytest.raises(RuntimeError, match="Mosaic"):
        eng.run_until_drained()
    assert not ex.degraded
    assert eng.stats["degraded"] == 0 and eng.stats["retries"] == 0


def test_cnn_degraded_plan_error_when_faults_persist():
    from repro.serve import CNNEngine, CNNServeConfig
    plan, CompiledPlan = cnn_setup()
    ex = CompiledPlan(plan, method="xla")
    eng = CNNEngine(ex, CNNServeConfig(max_batch=4))
    with FaultPlan([FaultSpec(site="cnn.batch_round", kind="raise", nth=1,
                              times=10**6)], seed=MATRIX_SEED):
        for r in make_images(3)():
            eng.submit(r)
        done = eng.run_until_drained()
    assert all(r.status == "error" for r in done)
    assert eng.stats["errors"] == 3


def test_cnn_corrupt_round_is_contained():
    from repro.serve import CNNEngine, CNNServeConfig
    plan, CompiledPlan = cnn_setup()
    rep = chaos.run_cnn_chaos(
        lambda: CNNEngine(CompiledPlan(plan, method="xla"),
                          CNNServeConfig(max_batch=4)),
        make_images(), FaultPlan(
            [FaultSpec(site="cnn.batch_round", kind="corrupt", nth=1)],
            seed=MATRIX_SEED))
    assert rep.ok, rep.summary()
    # round 1 (4 images) poisoned + contained; round 2 (2 images) survives
    assert len(rep.poisoned) == 4 and len(rep.survivors) == 2


def test_cnn_deadline_and_shedding():
    from repro.serve import CNNEngine, CNNServeConfig, ImageRequest
    plan, CompiledPlan = cnn_setup()
    ex = CompiledPlan(plan, method="xla")
    # shedding: queue capped below the submitted count
    eng = CNNEngine(ex, CNNServeConfig(max_batch=2, max_queue=3,
                                       shed_policy="reject"))
    shed = 0
    for r in make_images(5)():
        try:
            eng.submit(r)
        except QueueFullError:
            shed += 1
    assert shed == 2 and eng.stats["shed"] == 2
    done = eng.run_until_drained()
    assert len(done) == 3 and all(r.status == "ok" for r in done)
    # deadline: already-expired requests never get a forward
    eng2 = CNNEngine(ex, CNNServeConfig(max_batch=2, deadline_s=1e-9))
    for r in make_images(2)():
        eng2.submit(r)
    done2 = eng2.run_until_drained()
    assert all(r.status == "timeout" for r in done2)
    assert eng2.stats["timeouts"] == 2 and eng2.stats["batch_rounds"] == 0


# ------------------------------------------------------------- config knobs


def test_resilience_knob_validation():
    from repro.check.config import check_serve_config, \
        check_cnn_serve_config
    from repro.serve.cnn import CNNServeConfig
    bad = ServeConfig(max_batch=4, deadline_s=-1.0, max_queue=2,
                      shed_policy="panic", max_retries=-1,
                      retry_backoff_s=-0.5)
    msgs = check_serve_config(bad)
    joined = "\n".join(msgs)
    for frag in ("deadline_s", "max_queue=2 is below max_batch=4",
                 "shed_policy", "max_retries", "retry_backoff_s"):
        assert frag in joined, f"missing {frag!r} in: {joined}"
    assert check_serve_config(ServeConfig(
        max_batch=4, deadline_s=5.0, max_queue=8, shed_policy="drop")) == []
    msgs = check_cnn_serve_config(CNNServeConfig(
        max_batch=4, deadline_s=0, max_queue=1, shed_policy="nope"))
    assert len(msgs) == 3
    with pytest.raises(ValueError, match="shed_policy"):
        Engine(tiny_cfg(), None, ServeConfig(shed_policy="nope"))
