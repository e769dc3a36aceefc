"""End-to-end driver: train a CNN built from a selectable paper primitive
for a few hundred steps on the synthetic image pipeline, with the full
production substrate — AdamW, cosine schedule, async checkpointing,
preemption-safe resume, NaN guard — then post-training-quantize it to the
integer-only path and compare accuracy (the paper's deployment flow).

Run:  PYTHONPATH=src python examples/train_cnn.py --primitive shift --steps 300
      PYTHONPATH=src python examples/train_cnn.py --primitive add --steps 150
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.data import DataConfig, IndexedDataset
from repro.models.convnet import CNNConfig, cnn_forward, cnn_loss, init_cnn, quantize_cnn
from repro.optim import OptConfig, apply_updates, init_opt_state
from repro.checkpoint import Checkpointer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--primitive", default="standard",
                    choices=["standard", "grouped", "dws", "shift", "add"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_cnn_ckpt")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = CNNConfig(primitive=args.primitive, widths=(16, 32, 64))
    dcfg = DataConfig(kind="image", global_batch=args.batch, image_size=32,
                      num_classes=10, seed=7)
    ds = IndexedDataset(dcfg)
    opt = OptConfig(lr=2e-3, warmup_steps=20, total_steps=args.steps,
                    weight_decay=1e-4, grad_clip=1.0)
    ckpt = Checkpointer(args.ckpt_dir, keep=2)

    params = init_cnn(cfg, jax.random.PRNGKey(0))
    state = init_opt_state(params, opt)
    start = 0
    if args.resume and ckpt.latest_step() is not None:
        tree, start = ckpt.restore({"params": params, "opt": state})
        params, state = tree["params"], tree["opt"]
        print(f"resumed from step {start}")

    @jax.jit
    def step_fn(params, state, batch):
        (loss, acc), grads = jax.value_and_grad(
            lambda p: cnn_loss(p, batch, cfg), has_aux=True,
            allow_int=True)(params)
        params, state, om = apply_updates(params, grads, state, opt)
        return params, state, {"loss": loss, "acc": acc, **om}

    t0 = time.perf_counter()
    skipped = 0
    for i in range(start, args.steps):
        batch = jax.tree_util.tree_map(jnp.asarray, ds.batch(i))
        new_params, new_state, m = step_fn(params, state, batch)
        if not bool(jnp.isfinite(m["loss"])):
            skipped += 1                      # NaN guard: reject the step
        else:
            params, state = new_params, new_state
        if (i + 1) % 50 == 0:
            ckpt.save(i + 1, {"params": params, "opt": state})
            print(f"step {i+1:4d} loss {float(m['loss']):.4f} "
                  f"acc {float(m['acc']):.3f} ({time.perf_counter()-t0:.0f}s)",
                  flush=True)
    ckpt.wait()

    # ---- evaluation: float vs integer-only (paper PTQ flow) --------------
    from repro.models.convnet import calibrate_bn
    test = jax.tree_util.tree_map(jnp.asarray, ds.batch(10_000))
    calib = jnp.asarray(ds.batch(20_000)["images"])
    params = calibrate_bn(params, cfg, calib)   # deployment BN re-estimation
    logits_f = cnn_forward(params, test["images"], cfg)
    acc_f = float(jnp.mean((jnp.argmax(logits_f, -1) == test["labels"])))
    int_fwd = quantize_cnn(params, cfg, calib)
    logits_q = int_fwd(test["images"])
    acc_q = float(jnp.mean((jnp.argmax(logits_q, -1) == test["labels"])))
    print(f"\nprimitive={args.primitive}  float acc={acc_f:.3f}  "
          f"int8-pow2 acc={acc_q:.3f}  drop={acc_f-acc_q:+.3f}  "
          f"nan_skipped={skipped}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
