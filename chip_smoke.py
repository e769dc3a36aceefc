"""Start-up proof on one TPU chip: both serving paths, real kernels.

Runs in ONE process and touches one chip. Phases, in order:

1. device  — the default device must be a TPU and Pallas must compile
   (not interpret); anything else exits non-zero, naming the platform.
2. cnn     — the paper's network (default ``CNNConfig``) for each of the
   five primitives, int8 and W4, lowered with ``method="pallas"`` and
   served through ``CNNEngine``; every request is checked against the same
   plan under ``method="xla"`` (argmax equal, logits within 1e-5) and the
   lowered program must hold ``tpu_custom_call`` kernels.
3. lm      — qwen2-0.5b at its published widths (random weights from
   ``--seed``) through the continuous ``Engine`` with a paged KV cache and
   ``precision="int8"`` (Pallas ``matmul_q8``); the greedy streams must
   equal those of a ``precision="int8-xla"`` engine token for token.

Any request not ``"ok"``, any degraded kernel, any plan degradation or
XLA fallback fails the run. Seconds printed on the way are set-up time
(compilation included), not speed figures; progress lines go to stderr.
On success stdout holds one line,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Run from the root of a checkout:  python chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

PRIMITIVES = ("standard", "grouped", "dws", "shift", "add")
CNN_IMAGES = 16
LOGIT_ATOL = 1e-5            # the float head's tolerance (forward_batch)
LM_REQUESTS = 8
LM_NEW_TOKENS = 32
LM_PROMPT_LENS = (32, 256)   # inclusive range of prompt lengths


class SmokeFailure(Exception):
    """A phase produced a wrong, degraded or fallen-back result."""


def log(phase: str, **fields):
    """Progress goes to stderr: stdout carries only the final result."""
    print(json.dumps({"phase": phase, **fields}), file=sys.stderr,
          flush=True)


def device_check():
    """The chip, as JAX reports it; raises SmokeFailure off the TPU."""
    import jax
    from repro.kernels.common import use_interpret
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    log("device", **info)
    if dev.platform != "tpu":
        raise SmokeFailure(f"default device platform is {dev.platform!r}, "
                           "not 'tpu': this check runs on the chip only")
    if use_interpret():
        raise SmokeFailure("Pallas kernels would run in interpret mode "
                           "(REPRO_PALLAS_INTERPRET): nothing would compile "
                           "for the chip")
    return info


def kernel_count(fn, *shapes) -> int:
    """Pallas TPU kernels in the program ``fn`` lowers to at ``shapes``."""
    import jax
    return jax.jit(fn).lower(*shapes).as_text().count("tpu_custom_call")


def _no_degradation(where: str):
    from repro.kernels import ops
    from repro.obs import metrics
    if ops.degraded():
        raise SmokeFailure(f"{where}: kernels degraded to their oracle: "
                           f"{ops.degraded()}")
    for name in ("graph.degraded", "graph.fallback.xla"):
        if metrics.counter(name).value:
            raise SmokeFailure(f"{where}: {name} = "
                               f"{metrics.counter(name).value}")


def cnn_phase(cfg, seed: int, *, bits: int, n_images: int = CNN_IMAGES,
              max_batch: int = 8) -> dict:
    """Serve ``n_images`` seeded images through CNNEngine on the Pallas
    plan and check each against the XLA plan; returns what was checked."""
    import jax
    import numpy as np
    from repro.graph import CompiledPlan, build_cnn_graph, lower
    from repro.models.convnet import init_cnn
    from repro.serve import CNNEngine, CNNServeConfig, ImageRequest

    key = jax.random.PRNGKey(seed)
    params = init_cnn(cfg, key)
    size = (cfg.image_size, cfg.image_size, cfg.in_channels)
    calib = jax.random.normal(jax.random.fold_in(key, 1), (16,) + size)
    plan = lower(build_cnn_graph(cfg), params, calib, weight_bits=bits)
    pallas = CompiledPlan(plan, method="pallas")
    xla = CompiledPlan(plan, method="xla")
    images = np.asarray(jax.random.normal(jax.random.fold_in(key, 2),
                                          (n_images,) + size), np.float32)

    bucket = CompiledPlan.batch_bucket(min(max_batch, n_images))
    kernels = kernel_count(pallas._forward,
                           jax.ShapeDtypeStruct((bucket,) + size, np.float32))
    if not kernels:
        raise SmokeFailure(f"cnn {cfg.primitive} W{bits}: no tpu_custom_call "
                           "in the lowered Pallas plan")
    t0 = time.perf_counter()
    jax.block_until_ready(pallas.forward_batch(images[:bucket]))
    compile_s = time.perf_counter() - t0

    eng = CNNEngine(pallas, CNNServeConfig(max_batch=max_batch))
    for uid in range(n_images):
        eng.submit(ImageRequest(uid, images[uid]))
    done = eng.run_until_drained()
    bad = [(r.uid, r.status, r.error) for r in done if r.status != "ok"]
    if len(done) != n_images or bad:
        raise SmokeFailure(f"cnn {cfg.primitive} W{bits}: {len(done)} of "
                           f"{n_images} finished, not ok: {bad}")
    if pallas.degraded or eng.stats["degraded"]:
        raise SmokeFailure(f"cnn {cfg.primitive} W{bits}: plan degraded")

    by_uid = {r.uid: r for r in done}
    worst = 0.0
    for lo in range(0, n_images, max_batch):
        want = np.asarray(xla.forward_batch(images[lo:lo + max_batch]))
        for i, row in enumerate(want):
            got = by_uid[lo + i].logits
            if int(np.argmax(got)) != int(np.argmax(row)):
                raise SmokeFailure(f"cnn {cfg.primitive} W{bits}: argmax of "
                                   f"image {lo + i} differs from xla")
            worst = max(worst, float(np.max(np.abs(got - row))))
    if worst > LOGIT_ATOL:
        raise SmokeFailure(f"cnn {cfg.primitive} W{bits}: logits differ "
                           f"from xla by {worst} > {LOGIT_ATOL}")
    _no_degradation(f"cnn {cfg.primitive} W{bits}")
    return {"primitive": cfg.primitive, "bits": bits, "images": n_images,
            "tpu_custom_calls": kernels, "max_abs_logit_diff": worst,
            "compile_s": compile_s}


def _lm_requests(vocab: int, seed: int, n: int, lens, new_tokens: int):
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    plens = rng.integers(lens[0], lens[1] + 1, size=n)
    prompts = [rng.integers(0, vocab, size=int(p)).astype(np.int32)
               for p in plens]
    return [Request(uid=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]


def lm_serve(cfg, params, precision: str, seed: int, *, max_len: int = 512,
             n: int = LM_REQUESTS, lens=LM_PROMPT_LENS,
             new_tokens: int = LM_NEW_TOKENS, max_batch: int = 8) -> dict:
    """Greedy streams (uid -> tokens) of one paged continuous Engine."""
    from repro.serve import Engine, ServeConfig
    eng = Engine(cfg, params, ServeConfig(
        max_batch=max_batch, max_len=max_len, kv_layout="paged",
        precision=precision))
    for r in _lm_requests(cfg.vocab, seed, n, lens, new_tokens):
        eng.submit(r)
    done = eng.run_until_drained()
    bad = [(r.uid, r.status, r.error) for r in done if r.status != "ok"]
    if len(done) != n or bad:
        raise SmokeFailure(f"lm {precision}: {len(done)} of {n} finished, "
                           f"not ok: {bad}")
    short = [r.uid for r in done if len(r.out_tokens) != new_tokens]
    if short:
        raise SmokeFailure(f"lm {precision}: requests {short} stopped "
                           f"before {new_tokens} tokens")
    _no_degradation(f"lm {precision}")
    return {r.uid: list(map(int, r.out_tokens)) for r in done}


def lm_phase(cfg, seed: int, **kw) -> dict:
    """The int8 Pallas engine's streams must equal the int8-xla engine's."""
    import jax
    from repro.models import api
    from repro.obs import metrics
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    dispatched = metrics.counter("kernels.dispatch.matmul.pallas")
    before = dispatched.value
    t0 = time.perf_counter()
    got = lm_serve(cfg, params, "int8", seed, **kw)
    pallas_s = time.perf_counter() - t0
    if dispatched.value == before:
        raise SmokeFailure("lm int8: no Pallas matmul was dispatched")
    t0 = time.perf_counter()
    want = lm_serve(cfg, params, "int8-xla", seed, **kw)
    xla_s = time.perf_counter() - t0
    diff = [u for u in want if got.get(u) != want[u]]
    if diff:
        raise SmokeFailure(f"lm: int8 streams differ from int8-xla for "
                           f"requests {diff}")
    return {"arch": cfg.name, "requests": len(got),
            "tokens": sum(map(len, got.values())),
            "int8_setup_s": pallas_s, "int8_xla_setup_s": xla_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of weights, images and prompts")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from the "
              "root of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache
    log("setup", compile_cache=enable_compile_cache())
    try:
        info = device_check()
        from repro.configs import get_config
        from repro.models.convnet import CNNConfig
        from repro.obs import metrics
        for prim in PRIMITIVES:
            for bits in (8, 4):
                log("cnn", **cnn_phase(CNNConfig(primitive=prim), args.seed,
                                       bits=bits))
        log("lm", **lm_phase(get_config("qwen2-0.5b"), args.seed))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    log("done", tune_analytic_fallback=metrics.counter(
        "tune.cache.analytic_fallback").value,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
