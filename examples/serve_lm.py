"""Serve a small LM through the engine, comparing schedulers.

Continuous batching (the default) prefills each request into a free KV slot
and refills slots between decode rounds; ``--scheduler static`` runs the
legacy drain strategy (batch runs to completion). ``--scheduler both``
runs the same workload through each and prints throughput / occupancy /
TTFT side by side — the §Serving experiment at example scale.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch qwen2-0.5b --requests 8
(the arch config is reduced for CPU; the full config is what the dry-run
lowers for the 256/512-chip meshes)
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import api
from repro.serve import Engine, Request, ServeConfig


def reduce_cfg(cfg):
    kw = dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
              vocab=512)
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(cfg.moe, num_experts=4, top_k=2,
                                        d_ff=128)
    if cfg.family == "hybrid":
        kw.update(n_layers=8, attn_period=8, attn_offset=4)
    if cfg.family == "encdec":
        kw["n_encoder_layers"] = 2
    return dataclasses.replace(cfg, **kw)


def make_requests(n, vocab, max_new, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(4, 16))
        # skew the decode lengths: every 4th request runs 4x longer — the
        # workload where slot refill visibly beats draining static batches
        reqs.append(Request(uid=i, prompt=rng.integers(
            0, vocab, (plen,)).astype(np.int32),
            max_new_tokens=max_new * 4 if i % 4 == 0 else max_new))
    return reqs


def run_one(scheduler, cfg, params, args):
    eng = Engine(cfg, params, ServeConfig(max_batch=4, max_len=128,
                                          scheduler=scheduler))
    t0 = time.perf_counter()
    for r in make_requests(args.requests, cfg.vocab, args.max_new):
        eng.submit(r)
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    for r in done[:4]:
        print(f"  req {r.uid}: +{len(r.out_tokens)} tokens "
              f"{r.out_tokens[:8]}...")
    toks = sum(len(r.out_tokens) for r in done)
    st = eng.stats
    print(f"  {len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s)\n  stats: {st}")
    return dict(tok_s=toks / dt, occupancy=st["occupancy"],
                ttft_ms=st["ttft_avg_s"] * 1e3, rounds=st["decode_steps"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--scheduler", default="both",
                    choices=["continuous", "static", "both"])
    args = ap.parse_args()

    cfg = reduce_cfg(get_config(args.arch))
    if cfg.family == "encdec":
        raise SystemExit("serve_lm drives decoder-only archs; "
                         "seamless uses examples/translate stub via engine API")
    params = api.init_params(cfg, jax.random.PRNGKey(0))

    scheds = (["continuous", "static"] if args.scheduler == "both"
              else [args.scheduler])
    results = {}
    for sched in scheds:
        print(f"\n--- scheduler={sched} ---")
        results[sched] = run_one(sched, cfg, params, args)
    if len(results) == 2:
        c, s = results["continuous"], results["static"]
        print(f"\ncontinuous vs static drain: "
              f"{c['tok_s']:.1f} vs {s['tok_s']:.1f} tok/s "
              f"({c['tok_s'] / s['tok_s']:.2f}x), occupancy "
              f"{c['occupancy']:.2f} vs {s['occupancy']:.2f}, "
              f"decode rounds {c['rounds']} vs {s['rounds']}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
