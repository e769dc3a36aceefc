"""The plain reference against the program's own float path at a tiny
size on the CPU: the same model, so any gap is rounding (the program
computes the LM in bfloat16). The int8 control misses more often."""
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.common import seed_key
from bench.tests import tiny


def _setup():
    conf = harness.merge(harness.load_json("configs", "qwen2-0.5b-bf16"),
                         tiny.LM["config"])
    conf["name"] = "tiny"
    ref = harness.load_module("configs", "qwen2-0.5b")
    w = ref.make_weights(conf, seed_key(5))
    tokens = np.random.default_rng(0).integers(0, 512, 64).astype(np.int32)
    return conf, ref, w, tokens


def _program_logits(conf, w, tokens):
    from repro.models import transformer as T
    from bench.systems.lm import model_config
    return np.asarray(T.forward(w, jnp.asarray(tokens[None]),
                                model_config(conf), attn_impl="full",
                                remat="none")[0], np.float64)


def test_lm_reference_matches_program_float_forward():
    conf, ref, w, tokens = _setup()
    prog = _program_logits(conf, w, tokens)
    # the program's float argmax is (near) the reference's best everywhere;
    # a random token is not
    picks = prog.argmax(-1).astype(np.int32)
    st = ref.stats(conf, w, tokens, picks, 256)
    assert st["gap"].shape == (64,) and float(np.max(st["gap"])) < 0.05
    assert np.mean(st["argmax"] == picks) > 0.9
    np.testing.assert_array_equal(st["gap"] > 0, st["argmax"] != picks)
    other = ref.stats(conf, w, tokens, (picks + 1) % 512, 256)["gap"]
    assert float(np.median(other)) > 0.2
    # served_stats scores each token in the context it was served in
    served = picks[39:63]
    seq = np.concatenate([tokens[:40], served])
    g_seq = ref.stats(conf, w, seq[:-1], np.r_[np.full(39, -1), served],
                      256)["gap"][39:]
    np.testing.assert_allclose(
        ref.served_stats(conf, w, tokens[:40], served, 256)["gap"], g_seq)


def test_control_quantizes_to_nearest():
    ref = harness.load_module("configs", "qwen2-0.5b")
    x = jnp.asarray([[1.27, -0.6349, 0.0051, 0.0049]])
    q, scale = ref._q8(x, -1)
    assert float(scale[0, 0]) == np.float32(1.27) / 127
    assert np.asarray(q).tolist() == [[127, -63, 1, 0]]
    # an int8 product of the codes, rescaled, lands within half a step
    w = jnp.asarray(np.random.default_rng(1).normal(size=(4, 3)), jnp.float32)
    got = np.asarray(ref._mm(x, w, True))
    want = np.asarray(x @ w)
    assert np.all(np.abs(got - want) < 4 * float(scale[0, 0]) *
                  np.abs(np.asarray(w)).max())


def test_control_misses_the_argmax_more_often_than_the_program():
    conf, ref, w, tokens = _setup()
    prog = _program_logits(conf, w, tokens).argmax(-1)
    f32 = ref.stats(conf, w, tokens, np.full(64, -1), 256)["argmax"]
    q8 = ref.stats(conf, w, tokens, np.full(64, -1), 256,
                   quant=True)["argmax"]
    assert np.mean(q8 != f32) > np.mean(prog != f32)
    assert 0 < np.mean(q8 != f32) < 0.5
