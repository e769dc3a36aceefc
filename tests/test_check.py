"""repro.check static-analysis tests: VMEM footprint model + schedule
verdicts, tune-cache audit of the committed artifact, int32 accumulator /
requant-shift range analysis over real lowered plans, dataflow abstract
interpretation (and rejection of tampered plans), build-time CompiledPlan
validation, candidate-space pruning, explicit-config rejection at the ops
layer, serve-config checks, and the AST lint rules on synthetic fixtures
plus the real tree (zero false positives is an acceptance bar)."""
import dataclasses
import os
import textwrap

import jax
import numpy as np
import pytest

from repro.check import (CheckError, audit_cache, check_cnn_serve_config,
                         check_serve_config, validate_plan)
from repro.check.astlint import lint_file, lint_paths
from repro.check.dataflow import check_plan
from repro.check.footprint import (check_schedule, kernel_footprint,
                                   parse_cache_key, summarize_audit,
                                   vmem_budget)
from repro.check.overflow import (INT32_MAX, check_plan_overflow,
                                  check_requant_shift, overflow_errors)
from repro.core import Primitives
from repro.graph import CompiledPlan, build_cnn_graph, lower
from repro.models.convnet import CNNConfig, init_cnn
from repro.tune import space
from repro.tune.space import (default_config, sig_conv2d, sig_depthwise2d,
                              sig_matmul)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lowered(prim, *, weight_bits=8):
    cfg = CNNConfig(primitive=prim, widths=(8, 12), image_size=16)
    params = init_cnn(cfg, jax.random.PRNGKey(1))
    calib = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 16, 3)) * 0.5
    return lower(build_cnn_graph(cfg), params, calib,
                 weight_bits=weight_bits)


# ------------------------------------------------------ footprint model ---

def test_footprint_terms_positive_and_within_reason():
    sig = sig_conv2d(4, 32, 32, 16, 32, 3)
    fp = kernel_footprint(sig, space.effective_config(
        sig, default_config("conv2d")), "int8")
    assert fp.total_bytes > 0
    terms = dict(fp.terms)
    assert set(terms) == {"img", "wts", "out", "acc"}
    assert all(v >= 0 for v in terms.values())


def test_w4_halves_the_weight_block():
    sig = sig_conv2d(1, 16, 16, 16, 16, 3)
    cfg = space.effective_config(sig, default_config("conv2d"))
    w8 = dict(kernel_footprint(sig, cfg, "int8").terms)["wts"]
    w4 = dict(kernel_footprint(sig, cfg, "w4a8").terms)["wts"]
    assert w4 * 2 == w8


def test_block_n_64_rejected_on_table2_shape():
    # acceptance bar: batching the whole Table-2 batch into one tile is
    # statically infeasible (the f32 accumulator alone fills the budget)
    sig = sig_conv2d(64, 32, 32, 16, 64, 3)
    v = check_schedule(sig, {"block_n": 64}, "int8")
    assert not v.ok
    assert any("exceeds" in e and "budget" in e for e in v.errors)
    assert v.footprint.total_bytes > vmem_budget()


def test_unknown_key_and_bad_value_are_errors():
    sig = sig_depthwise2d(1, 16, 16, 8, 3)
    assert not check_schedule(sig, {"block_z": 4}, "int8").ok
    assert not check_schedule(sig, {"block_c": 0}, "int8").ok
    assert not check_schedule(sig, {"block_c": "8"}, "int8").ok


def test_degradation_is_a_warning_not_an_error():
    sig = sig_conv2d(1, 8, 8, 8, 8, 3)
    v = check_schedule(sig, {"block_co": 128}, "int8")
    assert v.ok and v.warnings
    assert v.effective["block_co"] == 8


def test_vmem_budget_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "4096")
    assert vmem_budget() == 4096
    sig = sig_matmul(256, 256, 256)
    assert not check_schedule(sig, {}, "int8").ok


def test_runner_cost_model_shares_the_footprint_model():
    # the tuner's soft VMEM penalty and the hard verdict must agree: any
    # schedule the cost model prices without penalty is feasible
    from repro.tune import runner
    sig = sig_conv2d(8, 32, 32, 16, 32, 3)
    for cfg in space.candidates(sig, "int8"):
        est = runner.estimate_s(sig, cfg, "int8")
        assert est > 0
        assert check_schedule(sig, cfg, "int8").ok


# ------------------------------------------------------ tune-cache audit ---

def test_committed_cache_schedules_all_feasible():
    path = os.path.join(ROOT, "artifacts", "tune_cache.json")
    if not os.path.exists(path):
        pytest.skip("no committed tune cache")
    rows = audit_cache(path)
    summ = summarize_audit(rows)
    assert summ["entries"] > 0
    assert summ["infeasible"] == []
    assert summ["warnings"] == 0      # degradation lands in notes


def test_parse_cache_key_roundtrip():
    sig = sig_conv2d(4, 32, 32, 16, 64, 3, groups=4)
    key = f"{sig.kernel}|{sig.key()}|int8|cpu+interpret"
    got_sig, dtype, backend = parse_cache_key(key)
    assert got_sig == sig and dtype == "int8" and backend == "cpu+interpret"
    with pytest.raises(ValueError):
        parse_cache_key("conv2d|bogus-shape|int8|tpu")


# ----------------------------------------------------- overflow analysis ---

@pytest.mark.parametrize("prim", Primitives)
@pytest.mark.parametrize("bits", [8, 4])
def test_lowered_plan_accumulators_proven_safe(prim, bits):
    plan = _lowered(prim, weight_bits=bits)
    bounds = check_plan_overflow(plan)
    assert bounds, "quantized plan must yield at least one bound"
    assert overflow_errors(bounds) == []
    for b in bounds:
        assert b.acc_max <= INT32_MAX
        assert b.headroom_bits > 0


def test_check_requant_shift_catches_each_failure_mode():
    assert check_requant_shift(1 << 20, 4) == []
    assert any("int32" in m for m in check_requant_shift(INT32_MAX + 1, 4))
    assert check_requant_shift(1 << 20, 40)          # |shift| >= 32
    assert check_requant_shift(1 << 20, 2.5)         # non-integer
    # rounding term 2^(s-1) pushes acc + round over int32
    assert check_requant_shift(INT32_MAX - 2, 3)
    # negative shift = left shift; wrap past int32 is caught
    assert check_requant_shift(1 << 28, -8)


def test_tampered_shift_caught_with_per_node_diagnostic():
    plan = _lowered("standard")
    node = next(n for n in plan.nodes if n.op == "qconv")
    node.out_fb = node.out_fb - 40          # shift now >= 32
    errs = overflow_errors(check_plan_overflow(plan))
    assert errs and any(e.startswith(f"{node.name}/") for e in errs)


def test_qbn_multiplier_budget_enforced():
    plan = _lowered("add")
    node = next(n for n in plan.nodes if n.op == "qbn")
    qp = dict(node.qparams)
    qp["a"] = np.asarray(qp["a"], dtype=np.int64) * 0 + (1 << 20)
    node.qparams = qp
    errs = overflow_errors(check_plan_overflow(plan))
    assert any("int16-range budget" in e for e in errs)


# ----------------------------------------------------- dataflow analysis ---

@pytest.mark.parametrize("prim", Primitives)
def test_lowered_plan_dataflow_clean(prim):
    assert [d for d in check_plan(_lowered(prim))
            if d.level == "error"] == []


def test_broken_scale_chain_rejected():
    plan = _lowered("standard")
    node = next(n for n in plan.nodes if n.op == "qconv")
    node.in_fb = node.in_fb + 3             # no longer the producer's out_fb
    diags = check_plan(plan)
    assert any(d.level == "error" and d.node == node.name for d in diags)


# ------------------------------------------- build-time plan validation ---

def test_compiled_plan_validates_at_build():
    plan = _lowered("standard")
    CompiledPlan(plan)                      # clean plan builds
    node = next(n for n in plan.nodes if n.op == "qconv")
    node.in_fb = node.in_fb + 3
    with pytest.raises(CheckError, match="static verification"):
        CompiledPlan(plan)
    CompiledPlan(plan, validate=False)      # explicit bypass still works


def test_validate_plan_message_lists_every_violation():
    plan = _lowered("standard")
    for n in plan.nodes:
        if n.op == "qconv":
            n.in_fb = n.in_fb + 3
    with pytest.raises(CheckError) as ei:
        validate_plan(plan)
    assert str(ei.value).count("  - ") >= 2


# ------------------------------------------------------ candidate space ---

def test_candidates_pruned_to_feasible_with_default_kept():
    sig = sig_conv2d(64, 32, 32, 16, 64, 3)
    cands = list(space.candidates(sig, "int8"))
    assert cands, "pruning must never empty the space"
    assert space.effective_config(sig, default_config("conv2d")) in [
        space.effective_config(sig, c) for c in cands]
    for c in cands[1:]:                     # default rides along unpruned
        assert check_schedule(sig, c, "int8").ok
    assert all(space.effective_config(sig, c).get("block_n", 1) < 64
               for c in cands[1:])


def test_tiling_rule_prunes_illegal_candidates():
    """Block shapes the TPU tiling rule refuses (last two block dims a
    multiple of (8, 128) or the whole extent) are errors, and the tuner's
    space never offers them: block_co=8 of 32 output channels is out,
    the whole 32 (or a 128-multiple) is in."""
    sig = space.sig_add_conv2d(1, 16, 16, 16, 32, 3)
    v = check_schedule(sig, {"block_co": 8}, "int8")
    assert not v.ok and any("tiling rule" in e for e in v.errors)
    assert check_schedule(sig, {"block_co": 32}, "int8").ok
    effs = [space.effective_config(sig, c)["block_co"]
            for c in space.candidates(sig, "int8")]
    assert effs and set(effs) == {32}
    # output tile width: a multiple of 8 sublanes, or the whole map
    conv = sig_conv2d(1, 16, 16, 8, 16, 3)
    assert not check_schedule(conv, {"block_w": 4}, "int8").ok
    assert check_schedule(conv, {"block_w": 8}, "int8").ok
    # matmul: N and K blocks are lane-aligned or whole; K tiles K exactly
    mm = sig_matmul(8, 4864, 896)
    assert not check_schedule(mm, {"bn": 64}, "int8").ok
    assert space.effective_config(mm, {"bk": 1024})["bk"] == 256
    for c in space.candidates(mm, "int8"):
        assert not any("tiling rule" in e
                       for e in check_schedule(mm, c, "int8").errors)


# ------------------------------------------------- ops explicit configs ---

def test_ops_rejects_explicit_infeasible_config(monkeypatch):
    from repro.kernels import ops
    x = np.zeros((1, 8, 8, 8), np.float32)
    w = np.zeros((3, 3, 8, 8), np.float32)
    with pytest.raises(CheckError, match="infeasible schedule"):
        ops.conv2d(x, w, config={"block_zz": 4})
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "1024")
    with pytest.raises(CheckError, match="exceeds"):
        ops.conv2d(x, w, config=dict(default_config("conv2d")))


def test_ops_rejects_explicit_config_breaking_tiling_rule():
    from repro.kernels import ops
    x = np.zeros((1, 8, 8, 8), np.int8)
    w = np.zeros((3, 3, 8, 32), np.int8)
    with pytest.raises(CheckError, match="tiling rule"):
        ops.add_conv2d(x, w, requant_shift=4, config={"block_co": 8})
    with pytest.raises(CheckError, match="tiling rule"):
        ops.matmul(np.zeros((8, 256), np.int8), np.zeros((256, 256), np.int8),
                   requant_shift=4, bn=64)


# --------------------------------------------------------- serve configs ---

def test_check_serve_config_enums_and_ranges():
    from repro.serve.engine import ServeConfig
    assert check_serve_config(ServeConfig()) == []
    errs = check_serve_config(ServeConfig(scheduler="bogus", max_batch=0,
                                          prefill_bucket=0))
    assert len(errs) == 3
    errs = check_serve_config(ServeConfig(kv_cache="int8",
                                          scheduler="static"))
    assert any("continuous" in e for e in errs)


def test_check_serve_config_strict_and_budget():
    from repro.configs.base import ModelConfig
    from repro.serve.engine import ServeConfig
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab=64)
    scfg = ServeConfig(max_len=8, prefill_bucket=16)
    assert check_serve_config(scfg, cfg, strict=False) == []
    assert any("prefill_bucket" in e
               for e in check_serve_config(scfg, cfg, strict=True))
    assert any("KV cache" in e for e in check_serve_config(
        ServeConfig(), cfg, hbm_budget=1 << 10))


def test_cnn_serve_config_checked_at_engine_init():
    from repro.serve.cnn import CNNServeConfig
    assert check_cnn_serve_config(CNNServeConfig()) == []
    assert check_cnn_serve_config(CNNServeConfig(max_batch=0))


# --------------------------------------------------------------- astlint ---

def _lint_src(tmp_path, src):
    p = tmp_path / "fixture.py"
    p.write_text(textwrap.dedent(src))
    return lint_file(str(p))


def test_lint_flags_index_map_default_args(tmp_path):
    fs = _lint_src(tmp_path, """
        import jax.experimental.pallas as pl
        def f(nb):
            spec = pl.BlockSpec((8, 8), lambda i, j, nb=nb: (i * nb, j))
    """)
    assert [f.rule for f in fs] == ["index-map-default-arg"]


def test_lint_flags_named_index_map_with_default(tmp_path):
    fs = _lint_src(tmp_path, """
        import jax.experimental.pallas as pl
        def build(nb):
            def imap(i, j, nb=nb):
                return (i * nb, j)
            return pl.BlockSpec((8, 8), index_map=imap)
    """)
    assert [f.rule for f in fs] == ["index-map-default-arg"]


def test_lint_flags_wall_clock_elapsed(tmp_path):
    fs = _lint_src(tmp_path, """
        import time
        def f():
            t0 = time.time()
            work()
            return time.time() - t0
    """)
    assert [f.rule for f in fs] == ["wall-clock-elapsed"]


def test_lint_flags_stop_before_sync(tmp_path):
    fs = _lint_src(tmp_path, """
        import time, jax
        def f(x):
            t0 = time.perf_counter()
            y = g(x)
            el = time.perf_counter() - t0
            jax.block_until_ready(y)
            return el
    """)
    assert [f.rule for f in fs] == ["timer-stop-before-sync"]


def test_lint_clean_patterns_not_flagged(tmp_path):
    fs = _lint_src(tmp_path, """
        import time, jax
        import jax.experimental.pallas as pl
        def f(x, nb):
            spec = pl.BlockSpec((8, 8), lambda i, j: (i * nb, j))
            t0 = time.perf_counter()
            y = g(x)
            jax.block_until_ready(y)
            el = time.perf_counter() - t0
            wall = time.time()           # bare stamp, not an interval
            return spec, el, wall
    """)
    assert fs == []


def test_lint_clean_on_real_tree():
    # acceptance bar: zero false positives over src/ and scripts/
    fs = lint_paths([os.path.join(ROOT, "src"),
                     os.path.join(ROOT, "scripts")])
    assert [str(f) for f in fs] == []
