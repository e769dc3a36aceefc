"""The trace reduction: busy time as a union, idle gaps, per-program and
custom-call sums, and host-span attribution, on hand-made events and on a
trace recorded on the chip."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

MS = 1_000_000  # ns


def _events():
    # window 0..100 ms; drain span 0..60, feed span 60..100
    spans = [("bench.window", 0, 100 * MS), ("bench.drain", 0, 60 * MS),
             ("bench.feed", 60 * MS, 100 * MS)]
    k = ("_kern", (("s8", (4, 8)),), (("s8", (4, 16)), ("s8", (16, 8))))
    ops = [("fusion.1", 5 * MS, 15 * MS, None),
           ("custom-call.7", 10 * MS, 20 * MS, k),        # overlaps fusion.1
           ("custom-call.7", 30 * MS, 34 * MS, k),
           ("copy.2", 31 * MS, 32 * MS, None),            # nested
           ("fusion.1", 90 * MS, 110 * MS, None),          # runs past the end
           ("fusion.9", -10 * MS, -5 * MS, None)]          # before the window
    mods = [("jit_step(3)", 4 * MS, 21 * MS), ("jit_step(3)", 29 * MS, 35 * MS),
            ("jit_other(4)", 89 * MS, 111 * MS)]
    return {"spans": spans,
            "devices": {"/device:TPU:0": {"ops": ops, "modules": mods}}}


def test_busy_is_a_union_clipped_to_the_window():
    r = tr.reduce_events(_events())
    assert r["window_s"] == pytest.approx(0.100)
    # [5,20] + [30,34] + [90,100] = 15 + 4 + 10 ms
    assert r["busy_s"] == pytest.approx(0.029)
    assert r["busy_s"] <= r["window_s"]


def test_programs_and_custom_calls():
    p = tr.reduce_events(_events())["programs"]
    assert p["jit_step"]["runs"] == 2
    assert p["jit_step"]["seconds"] == pytest.approx(0.023)
    assert p["jit_step"]["custom_calls"] == 2
    assert p["jit_step"]["custom_call_seconds"] == pytest.approx(0.014)
    assert p["jit_other"]["custom_calls"] == 0
    assert p["jit_other"]["seconds"] == pytest.approx(0.011)


def test_kernel_calls_by_shape():
    (k,) = tr.reduce_events(_events())["kernels"]
    assert k["kernel"] == "_kern" and k["calls"] == 2
    assert k["out"] == (("s8", (4, 8)),)
    assert k["seconds"] == pytest.approx(0.014)


def test_op_info_parses_hlo_instructions():
    name, kern = tr.op_info(
        "%_matmul.20 = s8[64,896]{1,0:T(8,128)(4,1)S(1)} custom-call("
        "s8[64,4864]{1,0:T(8,128)(4,1)} %a, s8[4864,896]{1,0} %b), "
        "custom_call_target=\"tpu_custom_call\"")
    assert name == "_matmul.20 s8[64,896]"
    assert kern == ("_matmul", (("s8", (64, 896)),),
                    (("s8", (64, 4864)), ("s8", (4864, 896))))
    name, kern = tr.op_info("%fusion.3 = bf16[8,16]{1,0} fusion(bf16[8,16]"
                            "{1,0} %x), kind=kLoop")
    assert name == "fusion.3 bf16[8,16]" and kern is None


def test_idle_gaps_longest_first_with_host_span():
    gaps = tr.reduce_events(_events())["breakdown"]["idle_gaps"]
    # idle: [0,5] drain, [20,30] drain, [34,90] straddles drain/feed
    assert [g[1] for g in gaps] == pytest.approx([0.056, 0.010, 0.005])
    assert gaps[1][0] == "bench.drain" and gaps[2][0] == "bench.drain"
    assert gaps[0][0] == "bench.feed"      # its midpoint, 62 ms, is in feed


def test_top_ops():
    ops = dict(tr.reduce_events(_events())["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.020)
    assert ops["custom-call.7"] == pytest.approx(0.014)
    assert "fusion.9" not in ops


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events({"spans": [], "devices": {}})


def test_trace_recorded_on_the_chip():
    """Four rounds of the paper CNN at batch 8 on a v5e (one bench.window,
    four bench.feed/bench.drain pairs): the first forward falls before the
    window on the device clock and is clipped out."""
    import gzip
    from jax.profiler import ProfileData
    raw = (Path(__file__).parents[1] / "testdata" /
           "cnn8_v5e.xplane.pb.gz").read_bytes()
    ev = tr.read_events(ProfileData.from_serialized_xspace(
        gzip.decompress(raw)))
    names = [s[0] for s in ev["spans"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.drain") == 4 and names.count("bench.feed") == 4
    r = tr.reduce_events(ev)
    assert 0 < r["busy_s"] < r["window_s"]
    fwd = r["programs"]["jit__forward"]
    assert fwd["runs"] == 3 and fwd["custom_calls"] == 18
    assert fwd["custom_call_seconds"] < fwd["seconds"]
    kernels = {(k["kernel"], k["out"][0][1]): k["calls"] for k in r["kernels"]}
    assert kernels == {
        ("_conv2d_im2col", (1, 8, 32, 32, 16)): 3,
        ("_conv2d_im2col", (1, 8, 16, 16, 32)): 3,
        ("_conv2d_im2col", (1, 8, 8, 8, 64)): 3,
        ("_maxpool2d", (8, 16, 16, 16)): 3,
        ("_maxpool2d", (8, 8, 8, 32)): 3,
        ("_maxpool2d", (8, 4, 4, 64)): 3}
    gaps = [g[1] for g in r["breakdown"]["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= r["window_s"] - r["busy_s"] + 1e-12
    assert all(g[0] == "bench.drain" for g in r["breakdown"]["idle_gaps"][:4])
