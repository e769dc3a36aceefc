"""End-to-end behaviour tests for the paper's system: train -> checkpoint ->
restore -> quantize -> serve, plus the paper's headline claims reproduced
by the cost models."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import ConvSpec, MCUModel
from repro.data import DataConfig, IndexedDataset
from repro.models import api
from repro.models.convnet import CNNConfig, cnn_forward, init_cnn, quantize_cnn


def test_cnn_train_quantize_deploy_pipeline(tmp_path):
    """The paper's full deployment flow: train float CNN (any primitive) ->
    BN-fold + PTQ to int8-pow2 -> integer inference agrees with float."""
    from repro.models.convnet import cnn_loss
    from repro.optim import OptConfig, apply_updates, init_opt_state
    cfg = CNNConfig(primitive="standard", widths=(8, 16), image_size=16)
    ds = IndexedDataset(DataConfig(kind="image", global_batch=32,
                                   image_size=16, seed=1))
    p = init_cnn(cfg, jax.random.PRNGKey(0))
    opt = OptConfig(lr=3e-3, warmup_steps=2, total_steps=60, weight_decay=0.0)
    st = init_opt_state(p, opt)

    @jax.jit
    def step(p, st, batch):
        (l, acc), g = jax.value_and_grad(lambda q: cnn_loss(q, batch, cfg),
                                         has_aux=True, allow_int=True)(p)
        p, st, _ = apply_updates(p, g, st, opt)
        return p, st, l

    for i in range(60):
        p, st, l = step(p, st, jax.tree_util.tree_map(jnp.asarray, ds.batch(i)))

    from repro.models.convnet import calibrate_bn
    x = jnp.asarray(ds.batch(100)["images"])
    y = jnp.asarray(ds.batch(100)["labels"])
    calib = jnp.asarray(ds.batch(200)["images"])
    p = calibrate_bn(p, cfg, calib)          # deployment BN re-estimation
    acc_f = float(jnp.mean(jnp.argmax(cnn_forward(p, x, cfg), -1) == y))
    int_fwd = quantize_cnn(p, cfg, calib)
    acc_q = float(jnp.mean(jnp.argmax(int_fwd(x), -1) == y))
    assert acc_f > 0.22                      # learned something (chance=0.1)
    assert acc_q > acc_f - 0.15              # PTQ drop bounded (paper flow)


def test_lm_train_checkpoint_serve_roundtrip(tmp_path):
    """Train a reduced LM, checkpoint, restore into bf16, serve batched."""
    from repro.optim import OptConfig
    from repro.train import LoopConfig, TrainConfig, Trainer
    from repro.checkpoint import Checkpointer
    from repro.serve import Engine, Request, ServeConfig

    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=2,
                              d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                              vocab=64)
    ds = IndexedDataset(DataConfig(kind="lm", vocab=64, seq_len=16,
                                   global_batch=4, seed=2))
    tr = Trainer(cfg, TrainConfig(opt=OptConfig(lr=1e-2, warmup_steps=1,
                                                total_steps=8)),
                 LoopConfig(total_steps=8, ckpt_every=8,
                            ckpt_dir=str(tmp_path), log_every=0),
                 ds, init_params_fn=lambda k: api.init_params(cfg, k))
    params, _, step, hist = tr.run()
    assert step == 8 and hist[-1]["loss"] < hist[0]["loss"] + 0.5

    # restore into serve dtype (bf16) and run the batched engine
    ck = Checkpointer(str(tmp_path))
    bf16_like = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.bfloat16
                            if jnp.issubdtype(x.dtype, jnp.floating)
                            else x.dtype), params)
    tree, got_step = ck.restore({"params": bf16_like,
                                 "opt": tr.init_or_restore()[1]})
    assert got_step == 8
    eng = Engine(cfg, tree["params"], ServeConfig(max_batch=2, max_len=32))
    for i in range(3):
        eng.submit(Request(uid=i, prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=3))
    done = eng.run_until_drained()
    assert len(done) == 3 and all(len(r.out_tokens) == 3 for r in done)


def test_paper_headline_claims():
    """Paper abstract claims, reproduced by the models the framework carries:
    (1) linear MACs<->energy without SIMD; (2) SIMD lowers latency+energy;
    (3) shift conv cheapest per param; (4) add conv same MACs as standard."""
    from benchmarks.common import r_squared
    mcu = MCUModel()
    macs, es = [], []
    for hk in (1, 3, 5, 7):
        s = ConvSpec(in_channels=8, out_channels=16, kernel_size=hk)
        macs.append(s.mac_count(32))
        es.append(mcu.energy_mj(s, 32, simd=False))
    assert r_squared(macs, es) > 0.99

    s = ConvSpec(in_channels=16, out_channels=16)
    assert mcu.latency_s(s, 32, simd=True) < mcu.latency_s(s, 32, simd=False)
    assert mcu.energy_mj(s, 32, simd=True) < mcu.energy_mj(s, 32, simd=False)

    shift = ConvSpec(primitive="shift", in_channels=16, out_channels=16)
    std = ConvSpec(primitive="standard", in_channels=16, out_channels=16)
    add = ConvSpec(primitive="add", in_channels=16, out_channels=16)
    assert shift.param_count() < std.param_count()
    assert add.mac_count(32) == std.mac_count(32)


def test_compile_cache_dir_from_env_or_checkout(monkeypatch):
    """Entry points keep JAX's persistent compilation cache where
    JAX_COMPILATION_CACHE_DIR says (setting nothing themselves), else at
    one fixed directory inside the checkout."""
    import os
    from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert DEFAULT_DIR.parent == type(DEFAULT_DIR)(root)
        assert DEFAULT_DIR.name == ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
