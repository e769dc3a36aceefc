"""The lfm2-8b-a1b cell's harness pieces on the CPU at tiny widths (widths
cut here only, never in the cell): a harness run of the cell is correct;
the system reads the engine's expert counters at the window's edges; the
cell's three per-layer metrics read them, and return None where a run has
nothing to read (no trace, or a program without the counters); the byte
count on hand-made counts."""
import dataclasses

import pytest

from bench import harness
from bench.common import Spans
from bench.expert_bytes import expert_bytes
from bench.peaks import peaks_for
from bench.tests import tiny

CELL = "lfm2-8b-a1b-11l-bf16.decode-backlog"
CONFIG = "lfm2-8b-a1b-11l-bf16"
METRICS = ("decode_round_ms.lfm2", "moe_roofline.lfm2",
           "device_idle_share.lfm2")

# layers 0-3 of the pattern (conv, conv, attention, conv): 2 dense FFNs,
# 2 expert layers
LFM2 = {"config": {"num_hidden_layers": 4, "hidden_size": 64,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "intermediate_size": 96, "moe_intermediate_size": 32,
                   "num_experts": 8, "num_experts_per_tok": 2,
                   "vocab_size": 512, "model": {"head_dim": 16},
                   "serve": {"max_batch": 4, "max_len": 256},
                   "check": {"requests": 3}},
        "traffic": tiny.LM["traffic"]}


def test_lfm2_backlog_sound_run_is_correct():
    res = tiny.run(CELL, LFM2)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    # the widest gap is not judged in this cell (lm_shortconv.System.check)
    assert set(res["checks"]) == {"mean_logit_gap", "missing"}


@pytest.fixture(scope="module")
def window():
    import os
    os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")
    conf = harness.merge(harness.load_json("configs", CONFIG),
                         LFM2["config"])
    conf["name"] = CONFIG
    mix = harness.merge(harness.load_json("traffic", "decode-backlog"),
                        LFM2["traffic"])
    system = harness.load_module("systems", conf["system"]).System(
        conf, harness.load_module("configs", conf["reference"]), mix,
        2 ** 33 + 11, 2.0, Spans(False))
    system.build()
    system.warm()
    return system, system.run_window(), conf


def test_lfm2_window_reads_the_expert_counters(window):
    system, win, conf = window
    cfg = system.mcfg
    assert win.delta("moe_experts_hit") > 0
    # every decode round routes all max_batch rows through every expert
    # layer; admissions' prefills add their buckets
    rows = conf["serve"]["max_batch"] * win.delta("decode_steps")
    assert win.delta("moe_assignments") >= \
        cfg.moe.top_k * cfg.n_moe_layers * rows > 0


def _read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


def test_lfm2_layer_metrics(window):
    system, win, conf = window
    ctx = {"window": win, "system": system, "conf": conf, "trace": None,
           "peaks": None}
    untraced = {m: _read(m, ctx) for m in METRICS}
    assert untraced["decode_round_ms.lfm2"] > 0
    assert untraced["moe_roofline.lfm2"] is None
    assert untraced["device_idle_share.lfm2"] is None
    trace = {"busy_s": 1.5, "window_s": 2.0,
             "kernels": [{"kernel": "moe_experts", "seconds": 0.25},
                         {"kernel": "paged_attention", "seconds": 9.0}]}
    ctx.update(trace=trace, peaks=peaks_for("TPU v5 lite"))
    moved = expert_bytes(win.delta("moe_experts_hit"),
                         win.delta("moe_assignments"), conf["hidden_size"],
                         conf["moe_intermediate_size"])
    assert _read("moe_roofline.lfm2", ctx) == pytest.approx(
        100.0 * moved / 819e9 / 0.25)
    assert _read("device_idle_share.lfm2", ctx) == pytest.approx(25.0)
    # a program without the counters (or without the kernel) reads None
    bare = {"kernels": []}
    assert _read("moe_roofline.lfm2", dict(ctx, trace=dict(trace, **bare))) \
        is None
    start = {k: v for k, v in win.start.items() if not k.startswith("moe_")}
    parent = dict(ctx, window=dataclasses.replace(win, start=start))
    assert _read("moe_roofline.lfm2", parent) is None


def test_expert_bytes_on_hand_made_counts():
    assert expert_bytes(0, 0, 2048, 1792) == 0
    # one decode round of the cell: 9 layers x 32 experts hit, 64 tokens
    # x top-4 x 9 layers routed
    assert expert_bytes(288, 2304, 2048, 1792) == \
        288 * 3 * 2048 * 1792 * 2 + 2304 * 2048 * (2 + 4)
    assert expert_bytes(1, 1, 2, 3, weight_bytes=4, act_bytes=1,
                        out_bytes=1) == 3 * 2 * 3 * 4 + 2 * 2
