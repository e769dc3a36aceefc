"""qwen2-0.5b: seeded weights and the plain reference (shared by every
qwen2-0.5b configuration, whatever precision it serves in).

The reference is the published decoder written out in ``jax.numpy`` at
float32 with every matmul at ``Precision.HIGHEST``: RMSNorm, q/k/v with
biases, rotary embedding on (d, d + head_dim/2) pairs, grouped-query causal
softmax attention (query head h reads kv head h // (n_heads / n_kv_heads)),
SwiGLU MLP, final RMSNorm, logits against the tied embedding. No cache, no
batching, no kernels, and nothing imported from the program.

The control is the same reference one precision below the configuration's
bfloat16: int8, rounded to nearest, in every projection of every layer
(weights per output channel, activations per token, int32 accumulation);
attention, norms and the head stay in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def dims(conf: dict) -> dict:
    """The sizes the model code needs, under short names."""
    return {"n_layers": conf["num_hidden_layers"], "d_model": conf["hidden_size"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["model"]["head_dim"],
            "d_ff": conf["intermediate_size"], "vocab": conf["vocab_size"],
            "act": conf["hidden_act"], "eps": conf["rms_norm_eps"],
            "theta": conf["rope_theta"]}


def make_weights(conf: dict, key):
    """All weights in one jitted call on the default device, float32, in
    the layout the serving engine reads."""
    m = dims(conf)

    @jax.jit
    def build(key):
        d, hq, hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
        ff, L, V = m["d_ff"], m["n_layers"], m["vocab"]
        ks = iter(jax.random.split(key, 16))

        def n(shape, scale):
            return jax.random.normal(next(ks), shape, jnp.float32) * scale

        return {
            "embed": n((V, d), 0.02),
            "final_norm": 1.0 + n((d,), 0.1),
            "layers": {
                "ln1": 1.0 + n((L, d), 0.1),
                "ln2": 1.0 + n((L, d), 0.1),
                "attn": {"wq": n((L, d, hq * dh), d ** -0.5),
                         "wk": n((L, d, hkv * dh), d ** -0.5),
                         "wv": n((L, d, hkv * dh), d ** -0.5),
                         "wo": n((L, hq * dh, d), (hq * dh) ** -0.5),
                         "bq": n((L, hq * dh), 0.02),
                         "bk": n((L, hkv * dh), 0.02),
                         "bv": n((L, hkv * dh), 0.02)},
                "mlp": {"w_gate": n((L, d, ff), d ** -0.5),
                        "w_up": n((L, d, ff), d ** -0.5),
                        "w_down": n((L, ff, d), ff ** -0.5)},
            },
        }

    return build(key)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, Dh), positions 0..S-1; rotates (i, i + Dh/2) pairs."""
    s, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _q8(x, axis):
    """Symmetric int8 codes of ``x``, rounded to nearest, and their scale
    (the largest magnitude along ``axis`` maps to 127)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale).astype(jnp.int8), scale


def _mm(x, w, quant):
    if not quant:
        return jnp.dot(x, w, precision=HI)
    xq, sx = _q8(x, -1)
    wq, sw = _q8(w, 0)
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _layer(m, h, lp, quant=False):
    s = h.shape[0]
    hq, hkv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = lp["attn"]
    x = _rms(h, lp["ln1"], m["eps"])
    q = (_mm(x, a["wq"], quant) + a["bq"]).reshape(s, hq, dh)
    k = (_mm(x, a["wk"], quant) + a["bk"]).reshape(s, hkv, dh)
    v = (_mm(x, a["wv"], quant) + a["bv"]).reshape(s, hkv, dh)
    q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
    rep = hq // hkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * dh ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(s, hq * dh)
    h = h + _mm(o, a["wo"], quant)
    f = lp["mlp"]
    x = _rms(h, lp["ln2"], m["eps"])
    g = _mm(x, f["w_gate"], quant)
    u = _mm(x, f["w_up"], quant)
    return h + _mm(jax.nn.silu(g) * u, f["w_down"], quant)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _stats(mkey, weights, tokens, targets, quant):
    m = dict(mkey)
    h = weights["embed"][tokens]
    h, _ = lax.scan(lambda hh, lp: (_layer(m, hh, lp, quant), None), h,
                    weights["layers"])
    h = _rms(h, weights["final_norm"], m["eps"])
    s = h.shape[0]
    chunk = 256

    def one(i):
        hc = lax.dynamic_slice_in_dim(h, i * chunk, chunk)
        tc = lax.dynamic_slice_in_dim(targets, i * chunk, chunk)
        logits = jnp.dot(hc, weights["embed"].T, precision=HI)
        got = jnp.take_along_axis(logits, jnp.maximum(tc, 0)[:, None], 1)[:, 0]
        gap = jnp.where(tc >= 0, jnp.max(logits, axis=-1) - got, 0.0)
        return gap, jnp.argmax(logits, axis=-1)

    gap, best = lax.map(one, jnp.arange(s // chunk))
    return gap.reshape(s), best.reshape(s).astype(jnp.int32)


def stats(conf: dict, weights, tokens, targets, pad_to: int,
          quant: bool = False):
    """At each position p of ``tokens``: ``gap``, how far the logit of
    ``targets[p]`` (the token after p; -1: none, gap 0) lies below the best
    logit there (0: the model's own argmax), and ``argmax``, the best id.
    ``quant`` runs the int8 control instead of the float32 reference.
    Inputs are right-padded to ``pad_to`` (a multiple of 256) so one
    program serves every length."""
    import numpy as np
    n = len(tokens)
    tok = np.zeros(pad_to, np.int32)
    tok[:n] = tokens
    tgt = np.full(pad_to, -1, np.int32)
    tgt[:n] = targets
    out = _stats(tuple(sorted(dims(conf).items())), weights, jnp.asarray(tok),
                 jnp.asarray(tgt), bool(quant))
    gap, best = (np.asarray(x)[:n] for x in out)
    return {"gap": gap, "argmax": best}


def served_stats(conf: dict, weights, prompt, served, pad_to: int,
                 quant: bool = False, targets=None):
    """:func:`stats` at the positions that produced each served token, in
    the context the server had then: the prompt and the tokens served
    before it. ``targets`` (one per served token) replaces the served
    tokens as the ids whose gap is read."""
    import numpy as np
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])[:-1]
    p, n = len(prompt), len(served)
    tgt = np.full(len(seq), -1, np.int32)
    tgt[p - 1:] = served if targets is None else targets
    out = stats(conf, weights, seq, tgt, pad_to, quant)
    return {k: v[p - 1:p - 1 + n] for k, v in out.items()}

