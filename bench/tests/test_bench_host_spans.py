"""The program's spans beside the harness's: idle time split by the
innermost span on the drain's thread, gaps named after the program's
spans, and ``trace_reduce``'s keys unchanged when there are none."""
import gzip
import threading
from pathlib import Path

import pytest

from bench import host_spans as hs
from bench import trace_reduce as tr

MS = 1_000_000  # ns
MAIN, CLOCK = ("/host:CPU", 0), ("/host:CPU", 1)


def _device(ops):
    return {"/device:TPU:0": {"ops": [(n, s, e, None) for n, s, e in ops],
                              "modules": []}}


def _events():
    """Window 0..100 ms on the clock thread; one drain 0..100 on the main
    thread holding admit 0..10 (prefill 2..8), upload 10..12, decode
    12..70, sample 70..90, upload 90..92; the device busy 3..7 and 13..69."""
    host = [("bench.window", 0, 100 * MS, CLOCK),
            ("bench.submit", 40 * MS, 41 * MS, CLOCK),
            ("bench.drain", 0, 100 * MS, MAIN),
            ("engine.drain", 0, 100 * MS, MAIN),
            ("engine.admit", 0, 10 * MS, MAIN),
            ("engine.prefill", 2 * MS, 8 * MS, MAIN),
            ("engine.upload", 10 * MS, 12 * MS, MAIN),
            ("engine.decode_round", 12 * MS, 70 * MS, MAIN),
            ("engine.sample", 70 * MS, 90 * MS, MAIN),
            ("engine.upload", 90 * MS, 92 * MS, MAIN)]
    ops = [("fusion.1", 3 * MS, 7 * MS), ("while.2", 13 * MS, 69 * MS)]
    return {"spans": [h[:3] for h in host], "host": host,
            "devices": _device(ops)}


def test_idle_split_by_the_innermost_span():
    r = hs.reduce_events(_events())
    idle = r["host_idle"]
    # idle: 0..3 (admit 2, prefill 1), 7..13 (prefill 1, admit 2, upload 2,
    # decode 1), 69..100 (decode 1, sample 20, upload 2, drain self 8)
    assert idle["engine.admit"] == pytest.approx(0.004)
    assert idle["engine.prefill"] == pytest.approx(0.002)
    assert idle["engine.upload"] == pytest.approx(0.004)
    assert idle["engine.decode_round"] == pytest.approx(0.002)
    assert idle["engine.sample"] == pytest.approx(0.020)
    assert idle["engine.drain"] == pytest.approx(0.008)
    assert "bench.submit" not in idle          # another thread
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["innermost_s"]["engine.drain"] == pytest.approx(0.008)
    assert r["spans"]["engine.upload"] == {"runs": 2,
                                           "seconds": pytest.approx(0.004)}


def test_gaps_named_after_the_program_spans():
    r = hs.reduce_events(_events(), long_gap_s=0.005)
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.031, 0.006, 0.003])
    # midpoints 84.5 (sample), 10 (admit ends, upload starts: the shorter
    # span holding it), 1.5 (admit)
    assert [g[0] for g in gaps] == ["engine.sample", "engine.upload",
                                    "engine.admit"]
    (a, b) = r["long_gaps"]
    assert a[:3] == ["engine.upload", pytest.approx(0.006),
                     pytest.approx(0.007)]
    assert b[0] == "engine.sample" and b[2] == pytest.approx(0.069)
    assert list(b[3]) == ["engine.sample", "engine.drain", "engine.upload",
                          "engine.decode_round"]


def test_no_drain_thread_leaves_idle_outside_spans():
    ev = _events()
    ev["host"] = [h for h in ev["host"] if h[3] == CLOCK]
    ev["spans"] = [h[:3] for h in ev["host"]]
    r = hs.reduce_events(ev)
    assert r["host_idle"] == {hs.OUTSIDE: pytest.approx(0.040)}


def _bench_only():
    """test_bench_trace's hand-made events: bench spans only."""
    spans = [("bench.window", 0, 100 * MS), ("bench.drain", 0, 60 * MS),
             ("bench.feed", 60 * MS, 100 * MS)]
    k = ("_kern", (("s8", (4, 8)),), (("s8", (4, 16)), ("s8", (16, 8))))
    ops = [("fusion.1", 5 * MS, 15 * MS, None),
           ("custom-call.7", 10 * MS, 20 * MS, k),
           ("custom-call.7", 30 * MS, 34 * MS, k),
           ("copy.2", 31 * MS, 32 * MS, None),
           ("fusion.1", 90 * MS, 110 * MS, None),
           ("fusion.9", -10 * MS, -5 * MS, None)]
    mods = [("jit_step(3)", 4 * MS, 21 * MS), ("jit_step(3)", 29 * MS, 35 * MS),
            ("jit_other(4)", 89 * MS, 111 * MS)]
    return {"spans": spans,
            "host": [(*s, MAIN) for s in spans],
            "devices": {"/device:TPU:0": {"ops": ops, "modules": mods}}}


KEYS = ("window_s", "busy_s", "programs", "kernels", "breakdown")


def test_bench_spans_alone_give_trace_reduce_output():
    ev = _bench_only()
    want = tr.reduce_events({"spans": ev["spans"], "devices": ev["devices"]})
    got = hs.reduce_events(ev)
    assert {k: got[k] for k in KEYS} == want
    assert set(got) == set(want) | {"host_idle", "innermost_s", "spans",
                                    "long_gaps"}


def test_recorded_chip_trace_reads_as_before():
    """The trace recorded on a v5e, which holds bench spans only: every key
    of trace_reduce's reduction reads the same through this module."""
    from jax.profiler import ProfileData
    raw = (Path(__file__).parents[1] / "testdata" /
           "cnn8_v5e.xplane.pb.gz").read_bytes()
    pd = ProfileData.from_serialized_xspace(gzip.decompress(raw))
    want = tr.reduce_events(tr.read_events(pd))
    ev = hs.read_events(pd)
    assert ev["spans"] == tr.read_events(pd)["spans"]
    got = hs.reduce_events(ev)
    assert {k: got[k] for k in KEYS} == want
    assert sum(got["host_idle"].values()) == pytest.approx(
        got["window_s"] - got["busy_s"])
    assert set(got["host_idle"]) <= {"bench.drain", "bench.feed", hs.OUTSIDE}


@pytest.mark.parametrize("spans,want", [
    ([], []),
    ([("a", 0, 10), ("b", 2, 4), ("c", 4, 6)],
     [(0, 2, "a"), (2, 4, "b"), (4, 6, "c"), (6, 10, "a")]),
    ([("a", 0, 2), ("b", 5, 7)], [(0, 2, "a"), (5, 7, "b")]),
    ([("a", 0, 4), ("b", 0, 4)], [(0, 4, "b")]),
])
def test_innermost_segments(spans, want):
    assert hs.innermost(spans) == want


def test_split_covers_every_interval():
    segs = [(0, 2, "a"), (2, 4, "b"), (6, 10, "a")]
    got = hs.split([(1, 3), (3, 8), (11, 12)], segs)
    assert got == {"a": 3, "b": 2, hs.OUTSIDE: 3}


def test_readings_per_round_and_idle_share():
    r = hs.reduce_events(_events())
    got = hs.readings(r)
    assert got["sample_ms"] == pytest.approx(20.0)
    assert got["admit_ms"] == pytest.approx(10.0)
    assert got["upload_ms"] == pytest.approx(4.0)
    assert got["decode_round_ms"] == pytest.approx(58.0)
    assert got["idle_share.engine.sample"] == pytest.approx(20.0)
    assert got["drain_self_share"] == pytest.approx(8.0)
    ev = _events()
    ev["host"] = [h for h in ev["host"] if h[0] != "engine.decode_round"]
    got = hs.readings(hs.reduce_events(ev))
    assert got["sample_ms"] is None and got["admit_ms"] is None


def test_program_spans_read_from_a_cpu_profile(tmp_path):
    """A small drain under the profiler on the CPU: its engine spans come
    back on the thread of the harness's bench.drain, another thread's span
    on its own."""
    import dataclasses

    import jax
    import numpy as np
    from jax.profiler import ProfileData, TraceAnnotation
    from repro.configs import get_config
    from repro.models import api
    from repro.serve import Engine, Request, ServeConfig
    cfg = dataclasses.replace(get_config("qwen2-0.5b"), n_layers=1,
                              d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                              vocab=64)
    eng = Engine(cfg, api.init_params(cfg, jax.random.PRNGKey(0)),
                 ServeConfig(max_batch=2, max_len=32, kv_layout="paged",
                             kv_block_size=8))
    for uid in range(2):
        eng.submit(Request(uid=uid, prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=2))

    def clock():
        with TraceAnnotation("bench.window"):
            pass

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        th = threading.Thread(target=clock)
        th.start()
        th.join()
        with TraceAnnotation("bench.drain"):
            eng.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    ev = hs.read_events(ProfileData.from_file(tr.find_trace(tmp_path)))
    threads = {}
    for name, s, e, th_key in ev["host"]:
        threads.setdefault(name, set()).add(th_key)
    assert {"engine.drain", "engine.arena_init", "engine.admit",
            "engine.prefill", "engine.upload", "engine.decode_round",
            "engine.sample"} <= set(threads)
    (drain_thread,) = threads["bench.drain"]
    assert all(threads[n] == {drain_thread} for n in threads
               if n.startswith("engine."))
    assert threads["bench.window"] != {drain_thread}
    assert ev["devices"] == {}          # no TPU plane on the CPU


def test_full_collections_marked_as_spans(tmp_path):
    """Inside gc_spans a full collection is a python.gc span on the
    profiler's clock; after it, collections are no longer marked."""
    import gc

    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with hs.gc_spans():
            with TraceAnnotation("engine.sample"):
                gc.collect()
        with TraceAnnotation("engine.upload"):
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    ev = hs.read_events(ProfileData.from_file(tr.find_trace(tmp_path)),
                        hs.PREFIXES + (hs.GC_SPAN,))
    (sample,) = [h for h in ev["host"] if h[0] == "engine.sample"]
    (upload,) = [h for h in ev["host"] if h[0] == "engine.upload"]
    marks = [h for h in ev["host"] if h[0] == hs.GC_SPAN]
    assert any(sample[1] <= g[1] <= g[2] <= sample[2] for g in marks)
    assert not any(upload[1] <= g[1] <= upload[2] for g in marks)

