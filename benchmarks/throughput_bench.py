"""Batched serving throughput through repro.graph (EXPERIMENTS.md §Throughput).

The tentpole claim of the batched/spatially-tiled kernel schedules: serving
images in microbatches beats the per-image loop because every image in a
batch shares the round's weight-block loads (the paper's Fig-3 data-reuse
quantity grows from Cx*BCO to N*Cx*BCO MACs per weight byte) and the
per-call dispatch overhead amortizes. Three row families per primitive:

  * ``throughput/<prim>/reuse/<node>`` — the analytic MACs/byte table: each
    conv node's per-weight-byte reuse at N=1 vs the bench batch, read off
    the tuned (or analytic-fallback) int8 schedule's effective blocks.
  * ``throughput/<prim>/batch<N>`` — batch-size sweep of delivered
    images/s through ``CompiledPlan.forward_batch`` (skipped under FAST).
  * ``throughput/<prim>/e2e`` — the acceptance row: paired-timed batched
    forward at N=8 vs the N=1 per-image loop on the SAME engine, with
    ``exact=`` flagging batched-vs-looped agreement (int8 trunk bit-exact;
    the float head compares at 1e-5, its argmax exactly).

Both sides run the xla integer oracle engine (fast under interpret-mode CI,
same engine both sides — the delta isolates batching, not pallas-vs-xla),
and the serve row drives the same plan through ``repro.serve.CNNEngine``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Primitives
from repro.graph import CompiledPlan, build_cnn_graph, lower
from repro.models.convnet import CNNConfig, init_cnn

from .common import FAST, emit

BATCH = 8


def _cfg(prim: str) -> CNNConfig:
    if FAST:
        return CNNConfig(primitive=prim, widths=(8, 12), image_size=16)
    return CNNConfig(primitive=prim, widths=(16, 32, 64), image_size=32)


def _paired_time(fn_a, fn_b, *, rounds: int = 7) -> tuple:
    """Median seconds for two thunks in interleaved A/B rounds (drift hits
    both sides equally — the batched-vs-loop ratio is the claim under
    test)."""
    fn_a(), fn_b()                       # warmup / compile both sides
    ta, tb = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        fn_a()
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn_b()
        tb.append(time.perf_counter() - t0)
    return float(np.median(ta)), float(np.median(tb))


def _reuse_rows(prim: str, plan, batch: int):
    """Fig-3 MACs-per-weight-byte table: reuse = block_n * Cx * BCO under
    the schedule the dispatch layer would run at this batch (int8)."""
    from repro import tune
    for node in plan.conv_nodes():
        spec = node.spec
        h, w = node.attrs["in_hw"]
        ci, co, hk = spec.in_channels, spec.out_channels, spec.kernel_size
        p = spec.primitive
        if p in ("standard", "grouped"):
            g = spec.groups if p == "grouped" else 1
            sig1 = tune.sig_conv2d(1, h, w, ci, co, hk, g)
            sigb = tune.sig_conv2d(batch, h, w, ci, co, hk, g)
            cx = ci // g
        elif p == "dws":                 # pointwise stage carries the reuse
            sig1 = tune.sig_conv2d(1, h, w, ci, co, 1, 1)
            sigb = tune.sig_conv2d(batch, h, w, ci, co, 1, 1)
            cx = ci
        elif p == "shift":
            sig1 = tune.sig_shift_conv2d(1, h, w, ci, co)
            sigb = tune.sig_shift_conv2d(batch, h, w, ci, co)
            cx = ci
        else:                            # add
            sig1 = tune.sig_add_conv2d(1, h, w, ci, co, hk)
            sigb = tune.sig_add_conv2d(batch, h, w, ci, co, hk)
            cx = ci
        e1 = tune.effective_config(sig1, tune.get_config(sig1, "int8"))
        eb = tune.effective_config(sigb, tune.get_config(sigb, "int8"))
        bco_key = "block_co" if "block_co" in e1 else "block_c"
        r1 = cx * e1[bco_key]
        rb = eb["block_n"] * cx * eb[bco_key]
        emit(f"throughput/{prim}/reuse/{node.name}", 0.0,
             f"macs={node.spec.mac_count(w)};macs_per_wbyte_n1={r1};"
             f"macs_per_wbyte_n{batch}={rb};reuse_gain={rb / max(r1, 1):.1f}x")


def main() -> None:
    for prim in Primitives:
        cfg = _cfg(prim)
        params = init_cnn(cfg, jax.random.PRNGKey(0))
        shape = (cfg.image_size, cfg.image_size, cfg.in_channels)
        calib = jax.random.normal(jax.random.PRNGKey(1), (4,) + shape) * 0.5
        plan = lower(build_cnn_graph(cfg), params, calib)
        ex = CompiledPlan(plan, method="xla")
        _reuse_rows(prim, plan, BATCH)

        x = jax.random.normal(jax.random.PRNGKey(2), (BATCH,) + shape) * 0.5

        # exact flag: batched == per-image loop (int8 trunk is bit-exact by
        # construction; the float gap->dense head is compared at 1e-5 and
        # by argmax, since XLA picks batch-size-dependent matmul kernels)
        batched = np.asarray(ex.forward_batch(x))
        looped = np.concatenate([np.asarray(ex(x[i:i + 1]))
                                 for i in range(BATCH)])
        exact = int(np.allclose(batched, looped, rtol=1e-5, atol=1e-6)
                    and (batched.argmax(-1) == looped.argmax(-1)).all())
        if not exact:                    # run.py reports a section failure
            raise RuntimeError(
                f"throughput/{prim}: batched forward diverged from the "
                "per-image loop — the batched kernel schedule is not exact")

        if not FAST:
            for n in (1, 2, 4, BATCH, 2 * BATCH):
                tp = ex.throughput(x[:1].repeat(n, 0), reps=3, warmup=1)
                emit(f"throughput/{prim}/batch{n}", tp["us_per_batch"],
                     f"images_per_s={tp['images_per_s']:.0f};"
                     f"us_per_image={tp['us_per_image']:.1f}")

        def run_batched():
            jax.block_until_ready(ex.forward_batch(x))

        def run_loop():
            for i in range(BATCH):
                jax.block_until_ready(ex(x[i:i + 1]))

        tb, tl = _paired_time(run_batched, run_loop)
        ips_b, ips_l = BATCH / tb, BATCH / tl
        emit(f"throughput/{prim}/e2e", tb * 1e6,
             f"loop_us={tl * 1e6:.1f};images_per_s={ips_b:.0f};"
             f"loop_images_per_s={ips_l:.0f};speedup={ips_b / ips_l:.2f}x;"
             f"exact={exact}")

    # serve wiring: the same plan behind the CNNEngine microbatcher
    from repro.serve import CNNEngine, CNNServeConfig, ImageRequest
    cfg = _cfg("standard")
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    shape = (cfg.image_size, cfg.image_size, cfg.in_channels)
    calib = jax.random.normal(jax.random.PRNGKey(1), (4,) + shape) * 0.5
    plan = lower(build_cnn_graph(cfg), params, calib)
    ex = CompiledPlan(plan, method="xla")
    eng = CNNEngine(ex, CNNServeConfig(max_batch=BATCH))
    n_req = 2 * BATCH + 3                # ragged final round
    rng = np.random.default_rng(0)
    # warm both batch buckets the drain will hit (BATCH and the ragged
    # round's pow2 bucket), then zero the counters: the row reports
    # steady-state serving throughput, not jit compilation
    warm = rng.normal(size=(n_req % BATCH,) + shape).astype(np.float32)
    jax.block_until_ready(ex.forward_batch(np.zeros((BATCH,) + shape,
                                                    np.float32)))
    jax.block_until_ready(ex.forward_batch(warm))
    eng.reset_stats()
    for uid in range(n_req):
        eng.submit(ImageRequest(uid, rng.normal(size=shape).astype(np.float32)
                                * 0.5))
    done = eng.run_until_drained()
    s = eng.stats
    assert len(done) == n_req and all(r.done for r in done)
    emit("throughput/serve/engine", 1e6 * s["images_done"]
         / max(s["images_per_s"], 1e-9) / max(s["batch_rounds"], 1),
         f"images={s['images_done']};rounds={s['batch_rounds']};"
         f"occupancy={s['occupancy']:.2f};"
         f"images_per_s={s['images_per_s']:.0f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    main()
