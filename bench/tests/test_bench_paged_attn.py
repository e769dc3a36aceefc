"""``paged_attn_roofline.backlog`` on a hand-made window and trace: the
bytes of the keys the window's decoded tokens attend to, over the paged
attention kernel's device seconds, against the v5e's HBM peak."""
import types

import numpy as np
import pytest

from bench import harness, peaks
from bench.common import Window

DIMS = {"n_layers": 24, "n_kv_heads": 2, "head_dim": 64}
PER_KEY = 24 * 2 * 2 * 64 * 2           # K and V, every layer, bf16


def _read(ctx):
    return harness.load_module("layer_metrics",
                               "paged_attn_roofline.backlog").read(ctx)


def _ctx(kernels, trace=True):
    reqs = [types.SimpleNamespace(prompt=np.zeros(p, np.int32))
            for p in (100, 10, 50)]
    # 100-token prompt: outputs 1 and 2 decoded at 101 and 102 keys (output
    # 0 came from the prefill); the 10-token one decoded nothing; the
    # 50-token one decoded outputs 2 and 3 at 52 and 53 keys
    win = Window(t0=0.0, t1=50.0, start={"out_lens": [0, 5, 2]},
                 end={"out_lens": [3, 5, 4]}, extra={"reqs": reqs})
    tr = {"window_s": 50.0, "busy_s": 40.0, "kernels": kernels} \
        if trace else None
    return {"window": win, "trace": tr,
            "system": types.SimpleNamespace(dims=DIMS),
            "peaks": peaks.peaks_for("TPU v5 lite") if trace else None}


def _kernel(name, seconds):
    return {"kernel": name, "out": (("f32", (64, 16, 128)),), "ins": (),
            "calls": 24, "seconds": seconds}


def test_share_of_the_hbm_roofline():
    least = (101 + 102 + 52 + 53) * PER_KEY / 819e9
    got = _read(_ctx([_kernel("paged_attention", least / 2),
                      _kernel("paged_attention", least / 2),
                      _kernel("_matmul", 10.0)]))
    assert got == pytest.approx(100.0)
    assert _read(_ctx([_kernel("paged_attention", 4 * least)])) \
        == pytest.approx(25.0)


@pytest.mark.parametrize("ctx", [
    _ctx([], trace=False),
    _ctx([]),
    _ctx([_kernel("_matmul", 1.0)]),
], ids=["no trace", "no kernels", "not the kernel"])
def test_nothing_to_read(ctx):
    assert _read(ctx) is None


def test_no_window_counters():
    ctx = _ctx([_kernel("paged_attention", 1.0)])
    ctx["window"].start = {}
    assert _read(ctx) is None
