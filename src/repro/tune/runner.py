"""Measured autotuner + analytic fallback cost model.

Two ways to pick a schedule, mirroring AutoTVM's measured-log / fallback
split (microtvm-blogpost-eval; "Not All Ops Are Created Equal!" motivates
the analytic half — MAC count alone misranks schedules, so the model scores
*data movement and occupancy*, not just arithmetic):

* :func:`autotune` — run every feasible config from ``space.candidates``
  through the real kernel, timing median-of-k with warmup. When the Pallas
  interpreter is active (no TPU) the measurement still ranks configs by the
  work the schedule issues, but the backend tag records the interpret mode
  so a TPU run never consumes interpreter numbers (the interpret-mode
  guard).

* :func:`analytic_config` — no measurement: a first-order TPU cost model
  built from the paper's analytic machinery (``ConvSpec.mac_count`` for
  arithmetic, ``core.energy.target_chip`` for peak FLOPs / HBM bandwidth /
  VMEM capacity) plus schedule-dependent terms: per-grid-step overhead, HBM
  traffic as a function of blocking, VPU/MXU lane utilization, and a hard
  VMEM-overflow penalty.

:func:`get_config` is the dispatch-layer entry point: memo -> persistent
cache -> analytic fallback.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from repro.check.footprint import (element_bytes, kernel_footprint,
                                   vmem_budget, weight_bytes)
from repro.core.energy import target_chip
from repro.core.primitives import ConvSpec
from repro.kernels.common import cdiv
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace

from . import cache as _cache
from . import space as _space
from .space import ShapeSig, effective_config


# First-order schedule constants (relative scoring is what matters).
GRID_STEP_OVERHEAD_S = 2e-6          # per grid step: DMA setup + dispatch
VPU_DERATE = 1.0 / 64.0              # VPU peak vs MXU peak (8x128 vs 128x128)
VMEM_PENALTY = 1e3                   # multiplier when a schedule overflows VMEM
LANE = 128
SUBLANE = 8


def backend_tag() -> str:
    """Cache-key backend tag; marks interpret mode so interpreter timings are
    never consumed by a real-TPU run (and vice versa)."""
    import jax
    from repro.kernels.common import use_interpret
    tag = jax.default_backend()
    if use_interpret():
        tag += "+interpret"
    return tag


# --------------------------------------------------------------------------
# Analytic fallback cost model
# --------------------------------------------------------------------------

def _util(block: int, tile: int = LANE) -> float:
    """Fraction of compute lanes a block of this width keeps busy."""
    if block <= 0:
        return 1e-9
    full = -(-block // tile) * tile
    return block / full


# Element/weight widths live in check.footprint now (the single source of
# truth the hard verifier shares); the old local names stay as aliases.
_bytes_of = element_bytes
_wbytes_of = weight_bytes


def _vmem_cost(fp) -> float:
    """Soft penalty from the SAME footprint model ``check.check_schedule``
    enforces as a hard verdict — the cost model and the verifier can never
    disagree about what fits."""
    return VMEM_PENALTY if fp.total_bytes > vmem_budget() else 1.0


def _tiles(sig: ShapeSig, eff: Dict[str, int]):
    """Grid geometry of one tiled-schedule kernel: (bn, bh, bw, tile
    steps-per-image-block). ``eff`` must be an effective config."""
    h, w = _space._out_hw(sig)
    bn, bh, bw = eff["block_n"], eff["block_h"], eff["block_w"]
    return bn, bh, bw, (sig.get("n") // bn) * cdiv(h, bh) * cdiv(w, bw)


def estimate_s(sig: ShapeSig, config: Dict[str, int], dtype: str) -> float:
    """Estimated seconds for one kernel invocation under ``config``.

    The tiled-grid kernels' traffic term reflects the batched schedule's
    weight reuse: one filter-block load per grid step now covers ``block_n``
    images (the Fig-3 reuse quantity grows from Cx*BCO to BN*Cx*BCO MACs
    per weight byte), while spatial tiles shrink the per-step image block —
    and with it the VMEM footprint — at the cost of halo re-reads.

    Per-step block byte counts come from ``check.footprint.kernel_footprint``
    — the same model ``check_schedule`` turns into a hard verdict — so a
    schedule the verifier rejects is exactly a schedule this model prices
    with the ``VMEM_PENALTY`` multiplier.
    """
    k = sig.kernel
    tpu = target_chip()

    if k == "conv2d":
        n, h, w = sig.get("n"), sig.get("h"), sig.get("w")
        ci, co, hk, g = (sig.get("ci"), sig.get("co"), sig.get("k"),
                         max(sig.get("g"), 1))
        cxg, cog = ci // g, co // g
        eff = effective_config(sig, config)
        bco = eff["block_co"]
        bn, bh, bw, sp_steps = _tiles(sig, eff)
        steps = sp_steps * g * (cog // bco)
        spec = ConvSpec(primitive="grouped" if g > 1 else "standard",
                        in_channels=ci, out_channels=co, kernel_size=hk,
                        groups=g, use_bias=False)
        flops = 2.0 * n * spec.mac_count(w)
        fp = kernel_footprint(sig, eff, dtype)
        t = dict(fp.terms)
        traffic = steps * (t["img"] + t["wts"] + t["out"])
        compute = flops / (tpu.peak_bf16_flops * _util(bco) * _util(cxg))
        return (_vmem_cost(fp)
                * (compute + traffic / tpu.hbm_bw + steps * GRID_STEP_OVERHEAD_S))

    if k == "depthwise2d":
        n, h, w, c, hk = (sig.get("n"), sig.get("h"), sig.get("w"),
                          sig.get("c"), sig.get("k"))
        eff = effective_config(sig, config)
        bc = eff["block_c"]
        bn, bh, bw, sp_steps = _tiles(sig, eff)
        steps = sp_steps * (c // bc)
        flops = 2.0 * n * h * w * c * hk * hk
        fp = kernel_footprint(sig, eff, dtype)
        t = dict(fp.terms)
        traffic = steps * (t["img"] + t["wts"] + t["out"])
        compute = flops / (tpu.peak_bf16_flops * VPU_DERATE * _util(bc))
        return (_vmem_cost(fp)
                * (compute + traffic / tpu.hbm_bw + steps * GRID_STEP_OVERHEAD_S))

    if k == "shift_conv2d":
        n, h, w, c, co = (sig.get("n"), sig.get("h"), sig.get("w"),
                          sig.get("c"), sig.get("co"))
        eff = effective_config(sig, config)
        bco = eff["block_co"]
        bn, bh, bw, sp_steps = _tiles(sig, eff)
        steps = sp_steps * (co // bco)
        flops = 2.0 * n * h * w * c * co
        fp = kernel_footprint(sig, eff, dtype)
        t = dict(fp.terms)
        traffic = steps * (t["img"] + t["wts"] + t["out"])
        compute = flops / (tpu.peak_bf16_flops * _util(bco) * _util(c))
        return (_vmem_cost(fp)
                * (compute + traffic / tpu.hbm_bw + steps * GRID_STEP_OVERHEAD_S))

    if k == "add_conv2d":
        n, h, w = sig.get("n"), sig.get("h"), sig.get("w")
        ci, co, hk = sig.get("ci"), sig.get("co"), sig.get("k")
        eff = effective_config(sig, config)
        bco = eff["block_co"]
        bn, bh, bw, sp_steps = _tiles(sig, eff)
        steps = sp_steps * (co // bco)
        # |a-b| broadcast: the (BN*BH*BW, Cx, BCO) intermediate is the VMEM
        # hog — the spatial tile is what keeps it bounded (the footprint's
        # acc term)
        flops = 3.0 * n * h * w * ci * co * hk * hk  # sub+abs+add per tap
        fp = kernel_footprint(sig, eff, dtype)
        t = dict(fp.terms)
        traffic = steps * (t["img"] + t["wts"] + t["out"])
        compute = flops / (tpu.peak_bf16_flops * VPU_DERATE * _util(bco, SUBLANE))
        return (_vmem_cost(fp)
                * (compute + traffic / tpu.hbm_bw + steps * GRID_STEP_OVERHEAD_S))

    if k == "maxpool2d":
        n, c, win, s = sig.get("n"), sig.get("c"), sig.get("k"), sig.get("s")
        hout, wout = _space._out_hw(sig)
        eff = effective_config(sig, config)
        bc = eff["block_c"]
        bn, bh, bw, sp_steps = _tiles(sig, eff)
        steps = sp_steps * (c // bc)
        flops = 1.0 * n * hout * wout * c * win * win    # VPU compares
        fp = kernel_footprint(sig, eff, dtype)
        t = dict(fp.terms)
        traffic = steps * (t["img"] + t["out"])
        compute = flops / (tpu.peak_bf16_flops * VPU_DERATE * _util(bc))
        return (_vmem_cost(fp)
                * (compute + traffic / tpu.hbm_bw + steps * GRID_STEP_OVERHEAD_S))

    if k == "causal_conv1d":
        b, l, d, kk = (sig.get("b"), sig.get("l"), sig.get("d"), sig.get("k"))
        eff = effective_config(sig, config)
        bl, bc = eff["block_l"], eff["block_c"]
        steps = b * (l // bl) * (d // bc)
        flops = 2.0 * b * l * d * kk
        fp = kernel_footprint(sig, eff, dtype)
        t = dict(fp.terms)
        traffic = steps * (t["img"] + t["wts"] + t["out"])
        compute = flops / (tpu.peak_bf16_flops * VPU_DERATE * _util(bc))
        return (_vmem_cost(fp)
                * (compute + traffic / tpu.hbm_bw + steps * GRID_STEP_OVERHEAD_S))

    if k == "matmul":
        m, kk, n = sig.get("m"), sig.get("k"), sig.get("n")
        eff = effective_config(sig, config)
        bm, bn, bk = eff["bm"], eff["bn"], eff["bk"]
        gi, gj, gk = -(-m // bm), -(-n // bn), -(-kk // bk)
        steps = gi * gj * gk
        flops = 2.0 * m * n * kk
        fp = kernel_footprint(sig, eff, dtype)
        t = dict(fp.terms)
        # A/B blocks stream every step; the output block lands once per
        # (i, j) after the k-axis accumulation
        traffic = steps * (t["a"] + t["b"]) + gi * gj * t["out"]
        compute = flops / (tpu.peak_bf16_flops
                           * _util(bn) * _util(bk) * _util(bm, SUBLANE))
        return (_vmem_cost(fp)
                * (compute + traffic / tpu.hbm_bw + steps * GRID_STEP_OVERHEAD_S))

    raise ValueError(f"unknown kernel {k!r}")


def analytic_config(sig: ShapeSig, dtype: str = "float32") -> Dict[str, int]:
    """Best config under the analytic model (no measurement)."""
    best, best_s = None, float("inf")
    for cfg in _space.candidates(sig, dtype):
        s = estimate_s(sig, cfg, dtype)
        if s < best_s:
            best, best_s = cfg, s
    assert best is not None
    return best


# --------------------------------------------------------------------------
# Measured autotuner
# --------------------------------------------------------------------------

def time_config(fn: Callable, *args, reps: int = 5, warmup: int = 2) -> float:
    """Median wall-clock microseconds per call (same protocol as
    benchmarks/common.time_fn; duplicated so src/ never imports benchmarks/)."""
    import jax
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def _kernel_call(kernel: str) -> Callable:
    """Kernel entry point taking (*arrays, config=...) — imported lazily to
    keep repro.tune importable without pulling the whole kernel layer."""
    from repro.kernels.common import use_interpret
    interp = use_interpret()
    if kernel == "conv2d":
        from repro.kernels.conv_im2col import conv2d_im2col as fn
    elif kernel == "depthwise2d":
        from repro.kernels.conv_dw import depthwise2d as fn
    elif kernel == "shift_conv2d":
        from repro.kernels.conv_shift import shift_conv2d as fn
    elif kernel == "add_conv2d":
        from repro.kernels.conv_add import add_conv2d as fn
    elif kernel == "causal_conv1d":
        from repro.kernels.conv1d_causal import causal_conv1d as fn
    elif kernel == "matmul":
        from repro.kernels.matmul_q8 import matmul as fn
    elif kernel == "maxpool2d":
        from repro.kernels.pool import maxpool2d as fn
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    return lambda args, cfg, kw: fn(*args, interpret=interp, config=cfg, **kw)


def autotune(kernel: str, sig: ShapeSig, args: Tuple, *,
             kwargs: Optional[dict] = None, dtype: str = "float32",
             reps: int = 5, warmup: int = 2,
             max_candidates: Optional[int] = None,
             verbose: bool = False) -> Tuple[Dict[str, int], float, list]:
    """Measure every candidate config on real arrays; return
    (best_config, best_us, [(config, us), ...]). ``kwargs`` are non-schedule
    kernel arguments (e.g. groups=, requant_shift=) held fixed across
    candidates; ``dtype`` selects the (wider for int8) candidate space."""
    from repro.kernels.common import use_interpret
    if use_interpret() and reps > 3:
        reps = 3                     # interpret-mode guard: interpreter is
        warmup = min(warmup, 1)      # slow & deterministic; fewer reps suffice
    call = _kernel_call(kernel)
    kw = kwargs or {}
    # throwaway pass: absorb process-level one-time costs (thread pools,
    # dtype-specific backend init) so the first timed candidate — always the
    # default schedule — is not systematically penalized
    call(args, _space.default_config(kernel), kw)
    results = []
    for i, cfg in enumerate(_space.candidates(sig, dtype)):
        if max_candidates is not None and i >= max_candidates:
            break
        # one span per measured candidate: an exported trace of a tuning run
        # shows the whole search, config and measured us on each slice
        with _obs_trace.span("tune.candidate", cat="tune", kernel=kernel,
                             shape=sig.key(), config=dict(cfg)) as sp:
            us = time_config(lambda a=args, c=cfg: call(a, c, kw),
                             reps=reps, warmup=warmup)
            sp.set(us=us)
        results.append((cfg, us))
        if verbose:
            print(f"  {kernel}/{sig.key()} {cfg} -> {us:.1f}us")
    best, best_us = min(results, key=lambda t: t[1])
    return best, best_us, results


def autotune_into(cache: _cache.TuneCache, kernel: str, sig: ShapeSig,
                  args: Tuple, dtype: str, **kw) -> Tuple[Dict[str, int], float]:
    """Autotune one (kernel, shape) and record the winner in ``cache``."""
    best, best_us, results = autotune(kernel, sig, args, dtype=dtype, **kw)
    default_us = next((us for cfg, us in results
                       if cfg == _space.default_config(kernel)), None)
    key = _cache.cache_key(kernel, sig.key(), dtype, backend_tag())
    cache.put(key, best, us=best_us, source="measured",
              default_us=default_us, n_candidates=len(results))
    return best, best_us


# --------------------------------------------------------------------------
# Whole-plan pre-tuning (repro.graph integration)
# --------------------------------------------------------------------------

def plan_jobs(plan, *, batch: int = 1) -> list:
    """Autotune jobs covering every kernel invocation of a lowered
    ``repro.graph`` Plan: one ``(kernel, sig, arrays, dtype, kwargs)`` tuple
    per distinct (kernel, shape) the executor will dispatch — dws layers
    contribute their depthwise AND pointwise stages, and int8 maxpool nodes
    contribute their own jobs. Shapes/requant shifts are read off the plan's
    annotated scales, so the timed epilogues are exactly the fused ones
    (requant + act) the executor runs. ``batch`` is the microbatch the
    schedules are searched at — tune at the batch you serve, since the
    block_n/block_h/block_w spaces (and the cache keys) depend on it."""
    import jax
    import jax.numpy as jnp
    from repro.core.quantize import QTensorW4

    def i8(shape, seed=0):
        return jax.random.randint(jax.random.PRNGKey(seed), shape, -100, 100,
                                  jnp.int32).astype(jnp.int8)

    def wkw(wq):
        """(extra kwargs, dtype key) for one weight leaf: W4-packed leaves
        tune under their own "w4a8" signature (halved weight traffic reranks
        the space) and carry their group shifts into the timed call."""
        if isinstance(wq, QTensorW4):
            return {"w_shifts": wq.shifts}, "w4a8"
        return {}, "int8"

    jobs, seen = [], set()

    def emit(kernel, sig, arrays, kwargs, dtype="int8"):
        k = (kernel, sig.key(), dtype)
        if k not in seen:
            seen.add(k)
            jobs.append((kernel, sig, arrays, dtype, kwargs))

    for node in plan.nodes:
        if node.op == "maxpool" and "in_hw" in node.attrs:
            h, w = node.attrs["in_hw"]
            c = node.attrs["in_ch"]
            win, s = node.attrs["window"], node.attrs["stride"]
            emit("maxpool2d", _space.sig_maxpool2d(batch, h, w, c, win, s),
                 (i8((batch, h, w, c)),), dict(window=win, stride=s))
            continue
        if node.op != "qconv":
            continue
        spec = node.spec
        h, w = node.attrs["in_hw"]
        ci, co, hk = spec.in_channels, spec.out_channels, spec.kernel_size
        p = spec.primitive
        if p in ("standard", "grouped"):
            g = spec.groups if p == "grouped" else 1
            wq = node.qparams["w"]
            kw, dt = wkw(wq)
            shift = node.in_fb + wq.frac_bits - node.out_fb
            emit("conv2d", _space.sig_conv2d(batch, h, w, ci, co, hk, g),
                 (i8((batch, h, w, ci)), wq.q),
                 dict(groups=g, requant_shift=shift, act=node.act, **kw), dt)
        elif p == "dws":
            w_dw, w_pw = node.qparams["w_dw"], node.qparams["w_pw"]
            mid_fb = node.qparams.get("mid_frac_bits", node.out_fb)
            kw_dw, dt_dw = wkw(w_dw)
            kw_pw, dt_pw = wkw(w_pw)
            emit("depthwise2d", _space.sig_depthwise2d(batch, h, w, ci, hk),
                 (i8((batch, h, w, ci)), w_dw.q[..., 0]),
                 dict(requant_shift=node.in_fb + w_dw.frac_bits - mid_fb,
                      **kw_dw), dt_dw)
            emit("conv2d", _space.sig_conv2d(batch, h, w, ci, co, 1, 1),
                 (i8((batch, h, w, ci)), w_pw.q),
                 dict(requant_shift=mid_fb + w_pw.frac_bits - node.out_fb,
                      act=node.act, **kw_pw), dt_pw)
        elif p == "shift":
            w_pw = node.qparams["w_pw"]
            kw, dt = wkw(w_pw)
            emit("shift_conv2d", _space.sig_shift_conv2d(batch, h, w, ci, co),
                 (i8((batch, h, w, ci)), node.qparams["shifts"],
                  w_pw.q[0, 0] if w_pw.q.ndim == 4 else w_pw.q),
                 dict(requant_shift=node.in_fb + w_pw.frac_bits - node.out_fb,
                      act=node.act, **kw), dt)
        elif p == "add":
            wq = node.qparams["w"]
            kw, dt = wkw(wq)
            x_pre = max(0, wq.frac_bits - node.in_fb)
            w_pre = max(0, node.in_fb - wq.frac_bits)
            acc_fb = max(node.in_fb, wq.frac_bits)
            emit("add_conv2d", _space.sig_add_conv2d(batch, h, w, ci, co, hk),
                 (i8((batch, h, w, ci)), wq.q),
                 dict(requant_shift=acc_fb - node.out_fb, x_preshift=x_pre,
                      w_preshift=w_pre, act=node.act, **kw), dt)
    return jobs


def autotune_plan(cache: _cache.TuneCache, plan, *, batch: int = 1,
                  **kw) -> list:
    """Pre-tune a whole plan's node set in one call: measure every distinct
    kernel invocation of ``plan`` and record the winners in ``cache`` (the
    executor then picks them up through the normal dispatch lookup).
    Returns ``[(kernel, sig, best_config, best_us), ...]``."""
    out = []
    for kernel, sig, arrays, dtype, kwargs in plan_jobs(plan, batch=batch):
        best, best_us = autotune_into(cache, kernel, sig, arrays, dtype,
                                      kwargs=kwargs, **kw)
        out.append((kernel, sig, best, best_us))
    return out


# --------------------------------------------------------------------------
# Dispatch-layer lookup: memo -> persistent cache -> analytic fallback
# --------------------------------------------------------------------------

def get_config(sig: ShapeSig, dtype: str) -> Dict[str, int]:
    key = _cache.cache_key(sig.kernel, sig.key(), str(dtype), backend_tag())
    hit = _cache.memo_get(key)
    if hit is not None:
        return hit["config"]
    pc = _cache.get_default_cache()
    entry = pc.get(key) if pc is not None else None
    if entry is None:
        # no tuned entry: the analytic cost model picks the schedule —
        # counted so untuned shapes are visible in the metrics snapshot
        _obs_metrics.counter("tune.cache.analytic_fallback").inc()
        entry = {"config": analytic_config(sig, str(dtype)),
                 "us": None, "source": "analytic"}
    else:
        _obs_metrics.counter("tune.cache.hit").inc()
    _cache.memo_put(key, entry)
    return entry["config"]
