"""repro.obs contracts: tracer span nesting + thread-safety, Chrome
trace-event schema validity, disabled-mode no-op, spans in the profiler's
trace (the engine's phase spans and their counters), histogram percentile
correctness vs numpy, registry in-place reset, serve-engine stats parity
(registry-backed ``stats`` keeps the legacy keys), request-lifecycle trace
lanes, monotonic request timestamps, and the bench_snapshot compare gate."""
import dataclasses
import glob
import importlib.util
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.graph import CompiledPlan, build_cnn_graph, lower
from repro.models import api
from repro.models.convnet import CNNConfig, init_cnn
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve import (CNNEngine, CNNServeConfig, Engine, ImageRequest,
                         Request, ServeConfig)

# ---------------------------------------------------------------- tracer ---


def test_span_nesting_order_and_args():
    tr = obs_trace.Tracer(enabled=True)
    with tr.span("outer", cat="t", a=1):
        with tr.span("inner", cat="t") as sp:
            sp.set(us=42)
    ev = tr.events()
    assert [(e["ph"], e["name"]) for e in ev] == [
        ("B", "outer"), ("B", "inner"), ("E", "inner"), ("E", "outer")]
    assert ev[0]["args"] == {"a": 1}          # ctor attrs ride on B
    assert ev[2]["args"] == {"us": 42}        # set() attrs ride on E
    ts = [e["ts"] for e in ev]
    assert ts == sorted(ts)


def test_complete_replays_recorded_stamps():
    tr = obs_trace.Tracer(enabled=True)
    t0 = tr._t0
    tr.complete("replayed", t0 + 1.0, t0 + 2.5, tid=7, n=3)
    b, e = tr.events()
    assert (b["ph"], e["ph"]) == ("B", "E")
    assert b["tid"] == e["tid"] == 7
    assert b["ts"] == pytest.approx(1.0e6)
    assert e["ts"] == pytest.approx(2.5e6)
    assert b["args"] == {"n": 3}


def test_disabled_mode_is_noop():
    tr = obs_trace.Tracer(enabled=False)
    # the Chrome sink records nothing; the span still times its block
    with tr.span("x", a=1) as sp:
        sp.set(b=2)
    assert sp.t1 >= sp.t0 > 0.0 and sp.seconds == sp.t1 - sp.t0
    tr.begin("x")
    tr.end("x")
    tr.complete("x", 0.0, 1.0)
    assert tr.events() == []


def test_env_gating(monkeypatch):
    for val, want in (("", False), ("0", False), ("1", True), ("yes", True)):
        monkeypatch.setenv(obs_trace.ENV_VAR, val)
        assert obs_trace.Tracer().enabled is want
    monkeypatch.delenv(obs_trace.ENV_VAR)
    assert obs_trace.Tracer().enabled is False


def test_traced_decorator(monkeypatch):
    tr = obs_trace.Tracer(enabled=True)
    monkeypatch.setattr(obs_trace, "TRACER", tr)

    @obs_trace.traced("work", cat="t")
    def f(x):
        return x + 1

    assert f(1) == 2
    assert [(e["ph"], e["name"]) for e in tr.events()] == [
        ("B", "work"), ("E", "work")]
    tr.disable()
    tr.clear()
    assert f(2) == 3 and tr.events() == []


def test_tracer_thread_safety():
    tr = obs_trace.Tracer(enabled=True)
    n_threads, n_spans = 8, 50

    def worker(k):
        for i in range(n_spans):
            with tr.span(f"t{k}", i=i):
                pass

    ts = [threading.Thread(target=worker, args=(k,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    ev = tr.events()
    assert len(ev) == 2 * n_threads * n_spans
    # per-tid: B/E balance and proper nesting (depth never negative)
    by_tid = {}
    for e in ev:
        by_tid.setdefault(e["tid"], []).append(e)
    for seq in by_tid.values():
        depth = 0
        for e in seq:
            depth += 1 if e["ph"] == "B" else -1
            assert depth >= 0
        assert depth == 0


def test_chrome_trace_schema(tmp_path):
    tr = obs_trace.Tracer(enabled=True)
    with tr.span("a", cat="c", k=1):
        with tr.span("b"):
            pass
    lane = obs_trace.next_lane()
    tr.complete("replay", tr._t0, tr._t0 + 0.001, tid=lane)
    path = tr.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        blob = json.load(f)
    assert blob["displayTimeUnit"] == "ms"
    assert blob["otherData"]["wall_clock_t0"] > 1e9       # wall clock, not
    events = blob["traceEvents"]                          # perf_counter
    assert events
    balance = {}
    for e in events:
        for field in ("ph", "name", "cat", "ts", "pid", "tid"):
            assert field in e, f"event missing {field}: {e}"
        assert e["ph"] in ("B", "E")
        balance[e["tid"]] = balance.get(e["tid"], 0) + (
            1 if e["ph"] == "B" else -1)
    assert all(v == 0 for v in balance.values())


def test_next_lane_unique():
    lanes = {obs_trace.next_lane() for _ in range(100)}
    assert len(lanes) == 100
    assert all(l >= obs_trace._LANE_BASE for l in lanes)


def _profile(tmp_path, fn):
    """Run ``fn`` under a jax.profiler session; the host events of the
    trace as (plane, line index, name, start_ns, end_ns, stats) tuples."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                out += [(plane.name, k, ev.name, ev.start_ns,
                         ev.start_ns + ev.duration_ns, dict(ev.stats))
                        for ev in line.events]
    return out


def test_spans_reach_the_profiler_trace(tmp_path):
    """With the Chrome sink off, spans (and traced calls) land in the
    profiler's trace with their attributes, set() ones included."""
    tr = obs_trace.Tracer(enabled=False)

    @obs_trace.traced("work")
    def work():
        return jax.numpy.ones(4).block_until_ready()

    def body():
        with tr.span("outer", uid=7) as sp:
            with tr.span("inner", cfg={"a": 1}):
                work()
            sp.set(result="ok")

    ev = {e[2]: e for e in _profile(tmp_path, body)}
    assert {"outer", "inner", "work"} <= set(ev)
    assert ev["outer"][5] == {"uid": 7, "result": "ok"}
    assert ev["inner"][5] == {"cfg": "{'a': 1}"}
    o, i, w = ev["outer"], ev["inner"], ev["work"]
    assert o[:2] == i[:2] == w[:2]                 # one plane and line
    assert o[3] <= i[3] <= w[3] <= w[4] <= i[4] <= o[4]
    assert tr.events() == []


def test_span_perf_counter_pair_feeds_chrome_stamps():
    tr = obs_trace.Tracer(enabled=True)
    with tr.span("s") as sp:
        time.sleep(0.002)
    b, e = tr.events()
    assert sp.seconds >= 0.002
    assert b["ts"] == pytest.approx((sp.t0 - tr._t0) * 1e6)
    assert e["ts"] == pytest.approx((sp.t1 - tr._t0) * 1e6)


# --------------------------------------------------------------- metrics ---


def test_counter_and_gauge():
    reg = obs_metrics.Registry()
    c = reg.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(7)
    g.set(3)
    assert g.value == 3.0
    with pytest.raises(TypeError):
        reg.gauge("c")          # type mismatch on an existing name


def test_counter_thread_safety():
    c = obs_metrics.Counter("c")
    ts = [threading.Thread(target=lambda: [c.inc() for _ in range(1000)])
          for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 8000


def test_histogram_percentiles_vs_numpy():
    # linear buckets at 0.01 resolution -> interpolated percentiles must
    # agree with numpy on uniform data to well within one bucket width
    buckets = np.linspace(0.0, 1.0, 101)[1:]
    h = obs_metrics.Histogram("h", buckets=buckets)
    rng = np.random.default_rng(0)
    xs = rng.uniform(0.0, 1.0, size=2000)
    for x in xs:
        h.observe(float(x))
    assert h.count == 2000
    assert h.mean == pytest.approx(float(np.mean(xs)), rel=1e-9)
    assert h.sum == pytest.approx(float(np.sum(xs)), rel=1e-9)
    for p in (5, 25, 50, 75, 95, 99):
        assert h.percentile(p) == pytest.approx(
            float(np.percentile(xs, p)), abs=0.02), f"p{p}"
    assert h.min == float(np.min(xs)) and h.max == float(np.max(xs))


def test_histogram_edge_cases():
    h = obs_metrics.Histogram("h")
    assert h.percentile(50) == 0.0 and h.mean == 0.0     # empty
    h.observe(0.25)
    # one sample: every percentile clamps to the observed value
    assert h.percentile(0) == h.percentile(50) == h.percentile(100) == 0.25
    with pytest.raises(ValueError):
        h.percentile(101)
    big = obs_metrics.Histogram("big")
    big.observe(1e6)            # above the last bucket -> overflow bin
    assert big.percentile(99) == 1e6
    with pytest.raises(ValueError):
        obs_metrics.Histogram("empty", buckets=[])


def test_registry_reset_in_place_keeps_handles():
    reg = obs_metrics.Registry()
    c = reg.counter("c")
    h = reg.histogram("h")
    c.inc(5)
    h.observe(1.0)
    reg.reset()
    assert c.value == 0.0 and h.count == 0
    assert reg.counter("c") is c          # same instrument, zeroed in place
    c.inc()
    assert reg.snapshot()["c"]["value"] == 1.0


def test_registry_snapshot_json():
    reg = obs_metrics.Registry()
    reg.counter("a").inc(2)
    reg.histogram("b").observe(0.5)
    snap = json.loads(reg.to_json())
    assert snap["a"] == {"type": "counter", "value": 2.0}
    assert snap["b"]["type"] == "histogram" and snap["b"]["count"] == 1
    assert {"p50", "p95", "p99", "mean", "min", "max"} <= set(snap["b"])


def test_kernel_dispatch_counters():
    import jax.numpy as jnp

    from repro.kernels import ops
    c_x = obs_metrics.counter("kernels.dispatch.maxpool2d.xla")
    c_p = obs_metrics.counter("kernels.dispatch.maxpool2d.pallas")
    v_x, v_p = c_x.value, c_p.value
    x = jnp.arange(64, dtype=jnp.float32).reshape(1, 8, 8, 1)
    ops.maxpool2d(x, window=2, method="xla")
    ops.maxpool2d(x, window=2, method="pallas")
    assert c_x.value == v_x + 1 and c_p.value == v_p + 1


# ------------------------------------------------------- engine parity -----


def _tiny_cfg():
    return dataclasses.replace(get_config("qwen2-0.5b"), n_layers=2,
                               d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                               vocab=64)


@pytest.fixture(scope="module")
def engine_setup():
    cfg = _tiny_cfg()
    return cfg, api.init_params(cfg, jax.random.PRNGKey(0))


def _drain(cfg, params, n_req=3, **scfg_kw):
    eng = Engine(cfg, params, ServeConfig(**scfg_kw))
    rng = np.random.default_rng(0)
    for uid in range(n_req):
        eng.submit(Request(uid=uid,
                           prompt=rng.integers(0, 64, (4,)).astype(np.int32),
                           max_new_tokens=3))
    done = eng.run_until_drained()
    return eng, done


# the pre-registry Engine.stats dict keys — the backward-compat contract
ENGINE_LEGACY_KEYS = {"prefills", "decode_steps", "tokens_out",
                      "requests_done", "occupancy", "ttft_avg_s",
                      "decode_tok_s"}
# block-pool gauges ride along for every layout (zero under contiguous)
ENGINE_POOL_KEYS = {"blocks_in_use", "blocks_free", "prefix_hit_rate"}
CNN_LEGACY_KEYS = {"batch_rounds", "images_done", "occupancy",
                   "latency_avg_s", "images_per_s"}


def test_engine_stats_parity_and_quantiles(engine_setup):
    cfg, params = engine_setup
    eng, done = _drain(cfg, params, n_req=3, max_batch=2, max_len=32)
    st = eng.stats
    assert ENGINE_LEGACY_KEYS <= set(st)
    assert ENGINE_POOL_KEYS <= set(st)
    # contiguous layout: pool gauges exist but stay zero
    assert st["blocks_in_use"] == 0 and st["prefix_hit_rate"] == 0.0
    assert st["requests_done"] == 3 and st["prefills"] == 3
    assert st["tokens_out"] == sum(len(r.out_tokens) for r in done) == 9
    assert 0.0 < st["occupancy"] <= 1.0
    assert st["decode_tok_s"] > 0.0
    # quantile keys ride along; p50 <= p99, all sane
    for k in ("ttft_p50_s", "ttft_p95_s", "ttft_p99_s", "tpot_avg_s",
              "queue_wait_avg_s", "queue_wait_p99_s"):
        assert k in st and st[k] >= 0.0
    assert st["ttft_p50_s"] <= st["ttft_p99_s"] + 1e-12
    # in-place reset: same handles, zeroed values
    eng.reset_stats()
    st2 = eng.stats
    assert st2["requests_done"] == 0 and st2["decode_tok_s"] == 0.0


def test_engine_monotonic_request_stamps(engine_setup):
    cfg, params = engine_setup
    _, done = _drain(cfg, params, n_req=2, max_batch=2, max_len=32)
    for r in done:
        # perf_counter stamps: monotonic lifecycle ordering is guaranteed
        assert r.submit_t <= r.admit_t <= r.first_token_t <= r.finish_t
        assert r.queue_wait_s >= 0.0
        assert r.submit_wall_t > 1e9          # the one wall-clock field


def test_engine_trace_lanes(engine_setup, tmp_path):
    cfg, params = engine_setup
    obs_trace.TRACER.clear()
    obs_trace.enable()
    try:
        _drain(cfg, params, n_req=3, max_batch=2, max_len=32)
        ev = obs_trace.TRACER.events()
    finally:
        obs_trace.disable()
        obs_trace.TRACER.clear()
    names = {e["name"] for e in ev}
    assert {"request", "queue_wait", "prefill", "generate",
            "engine.prefill", "engine.decode_round",
            "engine.drain"} <= names
    # one lane per retired request, each a balanced well-nested stack
    req_b = [e for e in ev if e["name"] == "request" and e["ph"] == "B"]
    lanes = {e["tid"] for e in req_b}
    assert len(req_b) == 3 and len(lanes) == 3
    assert all(t >= obs_trace._LANE_BASE for t in lanes)
    for lane in lanes:
        seq = [e for e in ev if e["tid"] == lane]
        depth = 0
        for e in seq:
            assert e["ts"] >= 0.0
            depth += 1 if e["ph"] == "B" else -1
            assert depth >= 0
        assert depth == 0
        # lifecycle sub-spans present on the lane
        assert {"queue_wait", "prefill", "generate"} <= {
            e["name"] for e in seq}


PAGED = dict(max_batch=2, max_len=32, kv_layout="paged", kv_block_size=8)
ENGINE_SPANS = {"engine.drain", "engine.arena_init", "engine.admit",
                "engine.prefill", "engine.upload", "engine.decode_round",
                "engine.sample"}


def test_engine_spans_in_profiler_trace(engine_setup, tmp_path):
    """A 3-request paged drain under the profiler: every phase span of the
    continuous loop is on the host plane, nested in engine.drain, with
    engine.prefill inside engine.admit."""
    cfg, params = engine_setup
    ev = _profile(tmp_path, lambda: _drain(cfg, params, n_req=3, **PAGED))
    spans = [e for e in ev if e[2].startswith("engine.")]
    assert ENGINE_SPANS <= {e[2] for e in spans}
    assert {"engine.prefix_lookup", "engine.block_alloc",
            "engine.block_free"}.isdisjoint(e[2] for e in ev)
    (drain,) = [e for e in spans if e[2] == "engine.drain"]
    for e in spans:
        assert e[:2] == drain[:2]                  # the drain's thread
        assert drain[3] <= e[3] <= e[4] <= drain[4]
    admits = [e for e in spans if e[2] == "engine.admit"]
    assert sorted(e[5]["uid"] for e in admits) == [0, 1, 2]
    assert all(e[5]["result"] == "ok" and e[5]["plen"] == 4
               and e[5]["new_blocks"] == 1 for e in admits)
    for p in (e for e in spans if e[2] == "engine.prefill"):
        assert any(a[3] <= p[3] <= p[4] <= a[4] for a in admits)
    # phases never overlap each other: they tile the loop
    phases = sorted((e[3], e[4]) for e in spans if e[2] in (
        "engine.arena_init", "engine.admit", "engine.upload",
        "engine.decode_round", "engine.sample"))
    assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))


PHASE_COUNTERS = ("serve.admit_time_s", "serve.upload_time_s",
                  "serve.sample_time_s", "serve.decode_time_s")


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_engine_phase_counters_fit_the_drain(engine_setup, layout):
    """The phase counters sum to no more than the drain's wall time, and
    they add up across drains (the engine's registry, not per drain)."""
    cfg, params = engine_setup
    kw = dict(PAGED, kv_layout=layout)
    eng = Engine(cfg, params, ServeConfig(**kw))
    rng = np.random.default_rng(0)
    seen, uid = [], 0
    for _ in range(2):
        for _ in range(3):
            eng.submit(Request(uid=uid, prompt=rng.integers(
                0, 64, (4,)).astype(np.int32), max_new_tokens=3))
            uid += 1
        t0 = time.perf_counter()
        eng.run_until_drained()
        wall = time.perf_counter() - t0
        vals = [eng.metrics.counter(k).value for k in PHASE_COUNTERS]
        prev = seen[-1] if seen else [0.0] * len(vals)
        assert all(v > p for v, p in zip(vals, prev))
        assert sum(vals) - sum(prev) <= wall
        seen.append(vals)
    if layout == "contiguous":   # only the post-sample len upload
        assert seen[1][1] > seen[0][1] > 0.0


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_engine_round_device_calls_per_drain(engine_setup, layout):
    """The device calls of a continuous round, as the benchmark's paged
    cells run them: over two drains, two ``engine.upload`` spans a decode
    round under the paged layout (``len`` + block table before the call,
    ``len`` after it) and one under the contiguous; one
    ``engine.decode_round`` span per ``serve.decode_steps``; one
    ``engine.arena_init`` per drain, plus one per rebuild; and a fresh
    ``Engine.pool`` each paged drain. A third drain whose decode round
    fails for good rebuilds the arena once."""
    from repro.faults import FaultPlan, FaultSpec
    cfg, params = engine_setup
    eng = Engine(cfg, params, ServeConfig(**dict(PAGED, kv_layout=layout)))
    rng = np.random.default_rng(0)

    def drain():
        for uid in range(3):
            eng.submit(Request(uid=uid, prompt=rng.integers(
                0, 64, (4,)).astype(np.int32), max_new_tokens=3))
        eng.run_until_drained()
        return eng.pool

    def count(name):
        return sum(e["ph"] == "B" and e["name"] == name
                   for e in obs_trace.TRACER.events())

    obs_trace.TRACER.clear()
    obs_trace.enable()
    try:
        pools = [drain(), drain()]
        rounds = eng.stats["decode_steps"]
        assert rounds > 0
        assert count("engine.upload") == \
            (2 if layout == "paged" else 1) * rounds
        assert count("engine.decode_round") == rounds
        assert count("engine.arena_init") == 2
        if layout == "paged":
            assert pools[0] is not None and pools[1] is not pools[0]
        with FaultPlan([FaultSpec(site="engine.decode_round", kind="raise",
                                  nth=1, times=3)], seed=0):
            drain()
        assert eng.stats["arena_rebuilds"] == 1
        assert count("engine.arena_init") == 3 + 1
        assert count("engine.decode_round") == eng.stats["decode_steps"]
    finally:
        obs_trace.disable()
        obs_trace.TRACER.clear()


def test_static_scheduler_sample_span(engine_setup, tmp_path):
    cfg, params = engine_setup
    box = {}
    ev = _profile(tmp_path, lambda: box.setdefault("eng", _drain(
        cfg, params, n_req=2, max_batch=2, max_len=32,
        scheduler="static")[0]))
    samples = [e for e in ev if e[2] == "engine.sample"]
    # the prefill's picks, then one per decode round
    assert len(samples) == 1 + box["eng"].stats["decode_steps"]
    assert box["eng"].metrics.counter("serve.sample_time_s").value > 0.0


def _cnn_plan():
    cfg = CNNConfig(primitive="standard", widths=(8, 12), image_size=16)
    params = init_cnn(cfg, jax.random.PRNGKey(1))
    calib = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 16, 3)) * 0.5
    return CompiledPlan(lower(build_cnn_graph(cfg), params, calib),
                        method="xla")


def test_cnn_engine_stats_parity_and_trace():
    ex = _cnn_plan()
    eng = CNNEngine(ex, CNNServeConfig(max_batch=4))
    rng = np.random.default_rng(0)
    obs_trace.TRACER.clear()
    obs_trace.enable()
    try:
        for uid in range(6):                   # 2 rounds: 4 + ragged 2
            eng.submit(ImageRequest(
                uid, rng.normal(size=(16, 16, 3)).astype(np.float32) * 0.5))
        done = eng.run_until_drained()
        ev = obs_trace.TRACER.events()
    finally:
        obs_trace.disable()
        obs_trace.TRACER.clear()
    assert len(done) == 6 and all(r.logits is not None for r in done)
    st = eng.stats
    assert CNN_LEGACY_KEYS <= set(st)
    assert st["images_done"] == 6 and st["batch_rounds"] == 2
    assert st["occupancy"] == pytest.approx(6 / 8)
    assert st["images_per_s"] > 0.0
    for k in ("latency_p50_s", "latency_p95_s", "latency_p99_s",
              "queue_wait_avg_s", "queue_wait_p99_s"):
        assert k in st and st[k] >= 0.0
    for r in done:
        assert r.submit_t <= r.admit_t <= r.finish_t
        assert r.submit_wall_t > 1e9
    names = {e["name"] for e in ev}
    assert {"image_request", "queue_wait", "execute",
            "cnn.batch_round"} <= names
    lanes = {e["tid"] for e in ev
             if e["name"] == "image_request" and e["ph"] == "B"}
    assert len(lanes) == 6


def test_cnn_engine_stats_isolated_per_engine():
    ex = _cnn_plan()
    a = CNNEngine(ex, CNNServeConfig(max_batch=2))
    b = CNNEngine(ex, CNNServeConfig(max_batch=2))
    rng = np.random.default_rng(1)
    a.submit(ImageRequest(0, rng.normal(size=(16, 16, 3))
                          .astype(np.float32)))
    a.run_until_drained()
    assert a.stats["images_done"] == 1
    assert b.stats["images_done"] == 0        # private registries


# --------------------------------------------------- bench_snapshot gate ---


def _load_bench_snapshot():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                        "bench_snapshot.py")
    spec = importlib.util.spec_from_file_location("bench_snapshot", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bs():
    return _load_bench_snapshot()


def _snap(us, *, tok_s=100.0, exact=1.0):
    return {
        "schema_version": 1, "fast": True, "backend": "cpu",
        "sections": {
            "serving": {"ok": True, "error": None, "rows": {
                "serve/static": {"us": us,
                                 "derived": {"tok_s": tok_s}},
            }},
            "quant": {"ok": True, "error": None, "rows": {
                "quant/conv/w=8": {"us": 50.0,
                                   "derived": {"exact": exact}},
            }},
        },
        "exact": {"quant/conv/w=8": exact},
        "headline": {}, "metrics": {},
    }


def test_parse_rows_and_coerce(bs):
    rows = bs.parse_rows(
        "serve/static,123.4,tok_s=99.5;exact=1\n"
        "noise line\nname,us_per_call,derived\n"
        "serve/speedup,0.0,continuous_over_static=2.31x\n")
    assert rows["serve/static"]["us"] == 123.4
    assert rows["serve/static"]["derived"] == {"tok_s": 99.5, "exact": 1.0}
    assert rows["serve/speedup"]["derived"][
        "continuous_over_static"] == 2.31


def test_compare_flags_injected_latency_regression(bs):
    prev, cur = _snap(100.0), _snap(120.0)     # +20% latency
    fails, _ = bs.compare(cur, prev, threshold=10.0, latency_hard=True)
    assert any("latency" in f and "serve/static" in f for f in fails)
    # warn-only downgrades it to a warning
    fails, warns = bs.compare(cur, prev, threshold=10.0, latency_hard=False)
    assert not fails
    assert any("serve/static" in w for w in warns)
    # under threshold: clean
    fails, warns = bs.compare(_snap(105.0), prev, threshold=10.0,
                              latency_hard=True)
    assert not fails and not warns


def test_compare_flags_throughput_drop(bs):
    prev = _snap(100.0, tok_s=100.0)
    cur = _snap(100.0, tok_s=70.0)            # -30% tok/s
    fails, _ = bs.compare(cur, prev, threshold=10.0, latency_hard=True)
    assert any("tok_s" in f for f in fails)


def test_compare_exactness_always_hard_fails(bs):
    prev, cur = _snap(100.0, exact=1.0), _snap(100.0, exact=0.0)
    fails, _ = bs.compare(cur, prev, threshold=10.0, latency_hard=False)
    assert any("exactness" in f for f in fails)


def test_compare_coverage_always_hard_fails(bs):
    prev, cur = _snap(100.0), _snap(100.0)
    del cur["sections"]["serving"]["rows"]["serve/static"]
    fails, _ = bs.compare(cur, prev, threshold=10.0, latency_hard=False)
    assert any("coverage" in f and "serve/static" in f for f in fails)
    cur2 = _snap(100.0)
    cur2["sections"]["quant"] = {"ok": False, "error": "boom", "rows": {}}
    fails, _ = bs.compare(cur2, prev, threshold=10.0, latency_hard=False)
    assert any("coverage" in f and "quant" in f for f in fails)


def test_compare_identical_is_clean(bs):
    fails, warns = bs.compare(_snap(100.0), _snap(100.0), threshold=10.0,
                              latency_hard=True)
    assert not fails and not warns
