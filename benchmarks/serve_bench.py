"""§Serving benchmark: static-drain vs continuous slot scheduling, plus
paged-vs-contiguous KV backing on a shared-prefix workload.

Workload 1 (scheduling): fixed-length prompts with SKEWED
``max_new_tokens`` (one long request per ``max_batch`` group, interleaved)
— the regime where a static batch drains at the pace of its slowest member
while continuous batching keeps retiring short sequences and refilling
their slots. Prompt lengths are fixed so both schedulers compile the same
prefill shape and the comparison isolates scheduling, not jit caching.

Workload 2 (paged KV, EXPERIMENTS.md §Paged-KV): every prompt shares one
long prefix (a system prompt) followed by a short unique suffix. The two
engines get the SAME KV byte budget — contiguous spends it on max_batch
fixed (max_len,) slots; paged spends it on a page pool, which (a) fits
~2x the concurrent requests because resident bytes track actual lengths,
and (b) serves prefix hits by prefilling only the suffix. The paired run
asserts bit-identical greedy streams (exact=1 in the gain row — the
perf gate's exactness guard), mean-concurrency ratio >= 1.5x, and lower
mean TTFT for paged.

Workload 3 (resilience, EXPERIMENTS.md §Resilience): the scheduling
workload drained twice on identically configured engines — once clean,
once under a fixed deterministic FaultPlan (injected decode/prefill
raises absorbed by bounded retries) with a capped queue shedding the
overflow (``shed_policy="drop"``). The row's ``exact=1`` only survives
if every non-shed request retires ``status="ok"`` with a token stream
bit-identical to the clean drain; ``degraded_ratio`` is the throughput
the faulted engine retained (faulted tok/s over clean tok/s).

Emits:
  serve/static,<us/token>,tok_s=...;occupancy=...;ttft_ms=...;rounds=...
  serve/continuous,<us/token>,...
  serve/speedup,0.0,continuous_over_static=<x>
  serve/prefix/contiguous,<us/token>,tok_s=...;conc=...;ttft_ms=...
  serve/prefix/paged,<us/token>,tok_s=...;conc=...;ttft_ms=...;hit_rate=...
  serve/prefix/gain,0.0,concurrent_ratio=...;ttft_speedup=...;exact=1
  serve/resilience,<us/token>,tok_s=...;degraded_ratio=...;shed_rate=...;
      retries=...;errors=...;exact=1

Engines are compile-warmed on a small drain and their stats reset before
the timed run. REPRO_BENCH_FAST=1 shrinks the workloads for CI.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import api
from repro.serve import Engine, Request, ServeConfig

from .common import FAST, emit

MAX_BATCH, MAX_LEN, PLEN = 4, 64, 8
# §Paged-KV workload: 32-token shared system prefix + 8-token unique
# suffix, 8 greedy tokens each; 8-position pages so the prefix spans 4
# hashable full blocks ((plen-1)//bs caps at 4 — the last prompt token is
# always recomputed for first-position logits)
SYS_LEN, SFX_LEN, PFX_NEW, PFX_BS = 32, 8, 8, 8


def tiny_cfg():
    return dataclasses.replace(
        get_config("qwen2-0.5b"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=256)


def workload(n: int, seed: int, long_new: int, short_new: int):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        reqs.append(Request(
            uid=i, prompt=rng.integers(0, 256, (PLEN,)).astype(np.int32),
            # one long request per max_batch group: each static batch stalls
            # on it while its short siblings' slots sit retired-but-held
            max_new_tokens=long_new if i % MAX_BATCH == 0 else short_new))
    return reqs


def run_sched(scheduler: str, cfg, params, n, long_new, short_new):
    eng = Engine(cfg, params, ServeConfig(
        max_batch=MAX_BATCH, max_len=MAX_LEN, scheduler=scheduler,
        prefill_bucket=PLEN))
    for r in workload(MAX_BATCH, seed=99, long_new=2, short_new=2):
        eng.submit(r)                   # compile warmup: prefill + decode
    eng.run_until_drained()
    eng.reset_stats()
    reqs = workload(n, seed=0, long_new=long_new, short_new=short_new)
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    assert len(done) == n and toks == sum(r.max_new_tokens for r in reqs)
    return toks / dt, toks, dt, eng.stats


def prefix_workload(n: int, seed: int, max_new: int):
    """n requests sharing one SYS_LEN-token prefix, unique SFX_LEN suffixes."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, 256, (SYS_LEN,)).astype(np.int32)
    return [Request(
        uid=i,
        prompt=np.concatenate(
            [sys_prompt, rng.integers(0, 256, (SFX_LEN,)).astype(np.int32)]),
        max_new_tokens=max_new) for i in range(n)]


def run_prefix(kv_layout: str, cfg, params, n: int):
    """Drain the shared-prefix workload under one KV layout.

    Both layouts get the same KV byte budget: contiguous holds MAX_BATCH
    slots of MAX_LEN positions; paged holds the equivalent pool
    (MAX_BATCH * MAX_LEN // PFX_BS usable pages + the garbage page) but
    offers 2x the slots — paged requests only pin pages for positions they
    actually occupy, so more of them fit in the same bytes.
    """
    if kv_layout == "paged":
        scfg = ServeConfig(
            max_batch=2 * MAX_BATCH, max_len=MAX_LEN, scheduler="continuous",
            prefill_bucket=PLEN, kv_layout="paged", kv_block_size=PFX_BS,
            kv_num_blocks=MAX_BATCH * MAX_LEN // PFX_BS + 1)
    else:
        scfg = ServeConfig(max_batch=MAX_BATCH, max_len=MAX_LEN,
                           scheduler="continuous", prefill_bucket=PLEN)
    eng = Engine(cfg, params, scfg)
    # warmup compiles every shape the timed run hits: full-prompt prefill
    # (the miss), suffix-only prefill + page gather (the hits), paged
    # decode, and the page-boundary growth at position SYS_LEN + SFX_LEN
    for r in prefix_workload(MAX_BATCH, seed=99, max_new=2):
        eng.submit(r)
    eng.run_until_drained()
    eng.reset_stats()
    reqs = prefix_workload(n, seed=0, max_new=PFX_NEW)
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    assert len(done) == n and toks == n * PFX_NEW
    st = eng.stats
    # mean resident requests per decode round — the concurrency the budget
    # actually bought (occupancy is normalised by each engine's own slots)
    conc = st["occupancy"] * scfg.max_batch
    streams = {r.uid: tuple(r.out_tokens) for r in done}
    return dict(tok_s=toks / dt, toks=toks, dt=dt, st=st, conc=conc,
                streams=streams)


def run_resilience(cfg, params, n: int):
    """§Resilience: clean vs faulted drain of the same capped-queue
    workload. Returns the emit payload fields; asserts the degradation
    contract (all non-shed ok, survivor streams bit-identical)."""
    from repro.faults import FaultPlan, FaultSpec, inject

    mq = max(MAX_BATCH, n - max(n // 4, 1))     # shed the overflow tail
    scfg = ServeConfig(max_batch=MAX_BATCH, max_len=MAX_LEN,
                       prefill_bucket=PLEN, max_queue=mq,
                       shed_policy="drop")

    def drain(fault_plan):
        eng = Engine(cfg, params, scfg)
        for r in workload(MAX_BATCH, seed=99, long_new=2, short_new=2):
            eng.submit(r)               # compile warmup
        eng.run_until_drained()
        eng.reset_stats()
        reqs = workload(n, seed=0, long_new=8, short_new=4)
        t0 = time.perf_counter()
        if fault_plan is None:
            for r in reqs:
                eng.submit(r)
            done = eng.run_until_drained()
        else:
            fault_plan.reset()
            with fault_plan:
                for r in reqs:
                    eng.submit(r)
                done = eng.run_until_drained()
        dt = time.perf_counter() - t0
        toks = sum(len(r.out_tokens) for r in done)
        return reqs, done, toks, dt, eng

    assert inject.active_plan() is None, \
        "serve_bench owns its fault schedule; unset REPRO_FAULTS"
    clean_reqs, _, clean_toks, clean_dt, _ = drain(None)
    # deterministic schedule: one decode round and one prefill attempt
    # fail transiently — both inside the bounded-retry budget
    plan = FaultPlan([
        FaultSpec(site="engine.decode_round", kind="raise", nth=2, times=2),
        FaultSpec(site="engine.prefill", kind="raise", nth=3, times=1),
    ], seed=0)
    reqs, done, toks, dt, eng = drain(plan)

    base = {r.uid: list(r.out_tokens) for r in clean_reqs
            if r.status == "ok"}
    shed = [r for r in reqs if r.status == "shed"]
    ok = [r for r in reqs if r.status == "ok"]
    exact = (len(ok) + len(shed) == n and len(shed) == n - mq
             and all(list(r.out_tokens) == base.get(r.uid) for r in ok)
             and len(plan.log) == 3)
    st = eng.stats
    return dict(
        tok_s=toks / dt, toks=toks, dt=dt,
        degraded_ratio=(toks / dt) / (clean_toks / clean_dt),
        shed_rate=len(shed) / n, retries=st["retries"],
        errors=st["errors"], exact=int(exact))


def main():
    cfg = tiny_cfg()
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    n = 8 if FAST else 16
    long_new, short_new = (16, 4) if FAST else (32, 4)
    tok_s = {}
    for sched in ("static", "continuous"):
        tok_s[sched], toks, dt, st = run_sched(
            sched, cfg, params, n, long_new, short_new)
        emit(f"serve/{sched}", dt * 1e6 / max(toks, 1),
             f"tok_s={tok_s[sched]:.1f};occupancy={st['occupancy']:.2f};"
             f"ttft_ms={st['ttft_avg_s'] * 1e3:.1f};rounds={st['decode_steps']}")
    emit("serve/speedup", 0.0,
         f"continuous_over_static={tok_s['continuous'] / tok_s['static']:.2f}x")

    # §Paged-KV: budget-matched shared-prefix comparison. The gain row's
    # exact=1 is the perf gate's exactness guard — it only survives if the
    # paged greedy streams stay bit-identical to contiguous.
    n_pfx = 8 if FAST else 16
    res = {lay: run_prefix(lay, cfg, params, n_pfx)
           for lay in ("contiguous", "paged")}
    assert res["paged"]["streams"] == res["contiguous"]["streams"], \
        "paged greedy streams diverged from contiguous"
    conc_ratio = res["paged"]["conc"] / res["contiguous"]["conc"]
    ttft_speedup = (res["contiguous"]["st"]["ttft_avg_s"]
                    / max(res["paged"]["st"]["ttft_avg_s"], 1e-9))
    assert conc_ratio >= 1.5, \
        f"paged concurrency {conc_ratio:.2f}x under the 1.5x budget claim"
    assert ttft_speedup > 1.0, \
        f"prefix hits did not lower mean TTFT ({ttft_speedup:.2f}x)"
    for lay in ("contiguous", "paged"):
        r = res[lay]
        extra = (f"tok_s={r['tok_s']:.1f};conc={r['conc']:.2f};"
                 f"ttft_ms={r['st']['ttft_avg_s'] * 1e3:.1f}")
        if lay == "paged":
            extra += f";hit_rate={r['st']['prefix_hit_rate']:.2f}"
        emit(f"serve/prefix/{lay}", r["dt"] * 1e6 / max(r["toks"], 1), extra)
    emit("serve/prefix/gain", 0.0,
         f"paged_prefix_toks={res['paged']['tok_s']:.1f};"
         f"concurrent_ratio={conc_ratio:.2f};ttft_speedup={ttft_speedup:.2f};"
         f"exact=1")

    # §Resilience: the degradation contract under a deterministic fault
    # schedule — exact=1 is mandatory (the perf gate rejects its absence)
    r = run_resilience(cfg, params, n)
    assert r["exact"] == 1, "faulted drain broke the degradation contract"
    emit("serve/resilience", r["dt"] * 1e6 / max(r["toks"], 1),
         f"tok_s={r['tok_s']:.1f};degraded_ratio={r['degraded_ratio']:.2f};"
         f"shed_rate={r['shed_rate']:.2f};retries={r['retries']};"
         f"errors={r['errors']};exact={r['exact']}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
