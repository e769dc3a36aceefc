"""lfm2-8b-a1b: seeded weights and the plain reference (shared by every
LFM2-8B-A1B configuration, whatever depth it keeps).

The reference is the published decoder (transformers' ``Lfm2Moe``) written
out in ``jax.numpy`` at float32 with every matmul at ``Precision.HIGHEST``.
Each layer is pre-norm (RMSNorm) with residuals, its operator then its FFN:

- gated short convolution: ``B, C, x = split3(h W_in)``, ``y = (C *
  conv(B * x)) W_out``, ``conv`` depthwise and causal over time with
  ``conv_L_cache`` taps per channel, no bias;
- attention: q, k, v projections without bias, RMSNorm of each q and k
  head, rotary embedding on (i, i + head_dim/2) pairs, grouped-query causal
  softmax attention (query head h reads kv head h // (heads / kv heads)),
  out projection;
- FFN: a dense SwiGLU on the first ``num_dense_layers`` layers; after
  them every one of the ``num_experts`` SwiGLU experts is evaluated for
  every token and summed under the routing weights, which are zero for the
  experts not picked: sigmoid scores of the router, the top
  ``num_experts_per_tok`` picked on score + expert bias, weighted by their
  scores over the picked scores' sum + 1e-6, times
  ``routed_scaling_factor``. No capacity, nothing dropped.

After the stack, a final RMSNorm and logits against the tied embedding.
No cache, no batching, no kernels, nothing imported from the program. Each
layer runs in its own call, which upcasts that layer's weights to float32,
so one layer's float32 copy is resident at a time.

Departures from the published model, each also in the configuration
file: the first ``num_hidden_layers`` entries of ``layer_types`` run (the
depth cut); the tie of the head to the embedding and the 1e-6 of the
normalisation are read from the LFM2 family's configs and the published
implementation; weights are random from the seed.

The control is the same reference one precision below the configuration's
bfloat16: int8, rounded to nearest, in every projection of every layer
(weights per output channel, activations per token, int32 accumulation);
the router, the short convolution's taps, attention, norms and the head
stay in float32, as the program keeps its router in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def dims(conf: dict) -> dict:
    """The sizes the model code needs, under short names."""
    d, hq = conf["hidden_size"], conf["num_attention_heads"]
    n = conf["num_hidden_layers"]
    return {"n_layers": n, "d_model": d, "n_heads": hq,
            "n_kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["model"]["head_dim"],
            "d_ff": conf["intermediate_size"], "vocab": conf["vocab_size"],
            "eps": conf["norm_eps"], "theta": conf["rope_theta"],
            "conv": conf["conv_L_cache"],
            "layer_types": tuple(conf["layer_types"][:n]),
            "n_dense": conf["num_dense_layers"],
            "experts": conf["num_experts"],
            "top_k": conf["num_experts_per_tok"],
            "expert_ff": conf["moe_intermediate_size"],
            "scale": float(conf["routed_scaling_factor"])}


def _layer_weights(m, key, kind, moe, dtype):
    d, hq, hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], \
        m["head_dim"]
    ks = iter(jax.random.split(key, 16))

    def n(shape, scale, mean=0.0):
        return (mean + jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    lp = {"ln1": n((d,), 0.1, 1.0), "ln2": n((d,), 0.1, 1.0)}
    if kind == "conv":
        lp["conv"] = {"in_proj": n((d, 3 * d), d ** -0.5),
                      "conv_w": n((m["conv"], d), m["conv"] ** -0.5),
                      "out_proj": n((d, d), d ** -0.5)}
    else:
        lp["attn"] = {"wq": n((d, hq * dh), d ** -0.5),
                      "wk": n((d, hkv * dh), d ** -0.5),
                      "wv": n((d, hkv * dh), d ** -0.5),
                      "wo": n((hq * dh, d), (hq * dh) ** -0.5),
                      "q_norm": n((dh,), 0.1, 1.0),
                      "k_norm": n((dh,), 0.1, 1.0)}
    if moe:
        e, ff = m["experts"], m["expert_ff"]
        lp["moe"] = {"router": n((d, e), d ** -0.5),
                     "bias": n((e,), 0.05),
                     "w_gate": n((e, ff, d), d ** -0.5),
                     "w_up": n((e, ff, d), d ** -0.5),
                     "w_down": n((e, ff, d), ff ** -0.5)}
    else:
        ff = m["d_ff"]
        lp["mlp"] = {"w_gate": n((d, ff), d ** -0.5),
                     "w_up": n((d, ff), d ** -0.5),
                     "w_down": n((ff, d), ff ** -0.5)}
    return lp


def make_weights(conf: dict, key):
    """All weights on the default device in ``conf["model"]["param_dtype"]``
    and the layout the serving engine reads: a list of per-layer dicts;
    each expert matrix (E, ff, d). Projections N(0,1)/sqrt(fan_in), the
    embedding N(0,0.02^2), norm weights 1+N(0,0.1^2), the selection bias
    N(0,0.05^2) (non-zero, so that picking and weighting differ). One
    jitted call per kind of layer, so the float32 draws of one layer at a
    time are resident."""
    m = dims(conf)
    dtype = jnp.dtype(conf["model"]["param_dtype"])
    k_emb, k_norm, k_layers = jax.random.split(key, 3)
    build = jax.jit(functools.partial(_layer_weights, m, dtype=dtype),
                    static_argnums=(1, 2))
    layers = [build(k, kind, i >= m["n_dense"])
              for i, (kind, k) in enumerate(zip(
                  m["layer_types"], jax.random.split(k_layers,
                                                     m["n_layers"])))]
    embed = jax.jit(lambda k: (jax.random.normal(
        k, (m["vocab"], m["d_model"]), jnp.float32) * 0.02).astype(dtype))
    norm = jax.jit(lambda k: (1.0 + jax.random.normal(
        k, (m["d_model"],), jnp.float32) * 0.1).astype(dtype))
    return {"embed": embed(k_emb), "final_norm": norm(k_norm),
            "layers": layers}


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
    """x (S, in) @ w (in, out)."""
    if not quant:
        return jnp.dot(x, w, precision=HI)
    xq, sx = _q8(x, -1)
    wq, sw = _q8(w, 0)
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _conv_layer(mkey, h, lp, quant):
    m = dict(mkey)
    lp = _f32(lp)
    p = lp["conv"]
    x = _rms(h, lp["ln1"], m["eps"])
    b, c, xx = jnp.split(_mm(x, p["in_proj"], quant), 3, axis=-1)
    bx = b * xx
    k, s = p["conv_w"].shape[0], bx.shape[0]
    padded = jnp.pad(bx, ((k - 1, 0), (0, 0)))
    conv = sum(padded[j:j + s] * p["conv_w"][j] for j in range(k))
    return h + _mm(c * conv, p["out_proj"], quant), padded


@functools.partial(jax.jit, static_argnums=(0, 3))
def _attn_layer(mkey, h, lp, quant):
    m = dict(mkey)
    lp = _f32(lp)
    a = lp["attn"]
    s = h.shape[0]
    hq, hkv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    x = _rms(h, lp["ln1"], m["eps"])
    q = _rms(_mm(x, a["wq"], quant).reshape(s, hq, dh), a["q_norm"], m["eps"])
    k = _rms(_mm(x, a["wk"], quant).reshape(s, hkv, dh), a["k_norm"],
             m["eps"])
    v = _mm(x, a["wv"], quant).reshape(s, hkv, dh)
    q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
    rep = hq // hkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * dh ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(s, hq * dh)
    return h + _mm(o, a["wo"], quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _mlp_ffn(mkey, h, lp, quant):
    m = dict(mkey)
    lp = _f32(lp)
    f = lp["mlp"]
    x = _rms(h, lp["ln2"], m["eps"])
    z = jax.nn.silu(_mm(x, f["w_gate"], quant)) * _mm(x, f["w_up"], quant)
    return h + _mm(z, f["w_down"], quant)


def routing(m, x, router, bias):
    """(S, E) routing weights: zero off the top-k, the picked sigmoid
    scores over their sum + 1e-6, times the routed scale."""
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision=HI))
    _, ids = lax.top_k(scores + bias, m["top_k"])
    picked = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], ids].set(1.0)
    w = scores * picked
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * m["scale"]


@functools.partial(jax.jit, static_argnums=(0, 3))
def _moe_ffn(mkey, h, lp, quant):
    m = dict(mkey)
    ln2 = lp["ln2"].astype(jnp.float32)
    p = lp["moe"]
    x = _rms(h, ln2, m["eps"])
    w = routing(m, x, p["router"].astype(jnp.float32),
                p["bias"].astype(jnp.float32))

    def expert(acc, e):
        up = lambda name: _mm(x, p[name][e].astype(jnp.float32).T, quant)
        z = jax.nn.silu(up("w_gate")) * up("w_up")
        y = _mm(z, p["w_down"][e].astype(jnp.float32), quant)
        return acc + lax.dynamic_slice_in_dim(w, e, 1, axis=1) * y, None

    y, _ = lax.scan(expert, jnp.zeros_like(h), jnp.arange(w.shape[1]))
    return h + y


@functools.partial(jax.jit, static_argnums=(0,))
def _head(mkey, h, final_norm, embed, targets):
    m = dict(mkey)
    h = _rms(h, final_norm.astype(jnp.float32), m["eps"])
    s = h.shape[0]
    chunk = 256

    def one(i):
        hc = lax.dynamic_slice_in_dim(h, i * chunk, chunk)
        tc = lax.dynamic_slice_in_dim(targets, i * chunk, chunk)
        logits = jnp.dot(hc, embed.astype(jnp.float32).T, precision=HI)
        got = jnp.take_along_axis(logits, jnp.maximum(tc, 0)[:, None], 1)[:, 0]
        gap = jnp.where(tc >= 0, jnp.max(logits, axis=-1) - got, 0.0)
        return gap, jnp.argmax(logits, axis=-1)

    gap, best = lax.map(one, jnp.arange(s // chunk))
    return gap.reshape(s), best.reshape(s).astype(jnp.int32)


def hidden(conf: dict, weights, tokens, quant: bool = False):
    """The last layer's hidden states (S, d) at every position of
    ``tokens``, before the final norm, and each short convolution's input
    ``B * x`` left-padded with ``K - 1`` zeros (S + K - 1, d)."""
    m = dims(conf)
    mkey = tuple(sorted(m.items()))
    h = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    convs = []
    for i, (kind, lp) in enumerate(zip(m["layer_types"], weights["layers"])):
        if kind == "conv":
            h, bx = _conv_layer(mkey, h, lp, bool(quant))
            convs.append(bx)
        else:
            h = _attn_layer(mkey, h, lp, bool(quant))
        ffn = _moe_ffn if i >= m["n_dense"] else _mlp_ffn
        h = ffn(mkey, h, lp, bool(quant))
    return h, convs


def logits(conf: dict, weights, tokens):
    """Float32 logits (S, vocab) at every position of ``tokens``: the full
    forward pass that prefill and decode through the cache are compared
    with."""
    h, _ = hidden(conf, weights, tokens)
    h = _rms(h, weights["final_norm"].astype(jnp.float32), conf["norm_eps"])
    return jnp.dot(h, weights["embed"].astype(jnp.float32).T, precision=HI)


def stats(conf: dict, weights, tokens, targets, pad_to: int,
          quant: bool = False):
    """At each position p of ``tokens``: ``gap``, how far the logit of
    ``targets[p]`` (the token after p; -1: none, gap 0) lies below the best
    logit there (0: the model's own argmax), and ``argmax``, the best id.
    ``quant`` runs the int8 control instead of the float32 reference.
    Inputs are right-padded to ``pad_to`` (a multiple of 256) so one
    program per kind of layer serves every length."""
    import numpy as np
    m = dims(conf)
    mkey = tuple(sorted(m.items()))
    n = len(tokens)
    tok = np.zeros(pad_to, np.int32)
    tok[:n] = tokens
    tgt = np.full(pad_to, -1, np.int32)
    tgt[:n] = targets
    h, _ = hidden(conf, weights, tok, quant)
    gap, best = (np.asarray(x)[:n] for x in _head(
        mkey, h, weights["final_norm"], weights["embed"], jnp.asarray(tgt)))
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
