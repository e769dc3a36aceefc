"""Operations each model step needs, computed from shapes alone (no
padding, no tiling: what the algorithm needs, not what a schedule happens
to do).

Integer matmuls count int8 operations (multiply and add as two); float
matmuls count FLOPs at the bf16 peak.
"""
from __future__ import annotations

from typing import Dict


def lm_token(cfg: dict, ctx: int, ffn_dtype: str = "int8") -> Dict[str, float]:
    """Model operations for one token at context length ``ctx`` (the keys
    it attends to, itself included) of a dense GQA decoder whose FFN runs
    in ``ffn_dtype`` ("int8", counted as int8 operations, or "bf16") and
    everything else in float.

    ``cfg`` holds ``n_layers``, ``d_model``, ``n_heads``, ``n_kv_heads``,
    ``head_dim``, ``d_ff``, ``vocab`` and ``act`` ("silu": three FFN
    matrices, otherwise two)."""
    d, hq, hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    dh, ff, L = cfg["head_dim"], cfg["d_ff"], cfg["n_layers"]
    proj = 2.0 * d * (hq * dh + 2 * hkv * dh) + 2.0 * hq * dh * d
    attn = 2.0 * 2.0 * hq * dh * ctx          # q.k and p.v
    f = (3 if cfg.get("act", "silu") == "silu" else 2) * 2.0 * d * ff
    head = 2.0 * d * cfg["vocab"]
    if ffn_dtype == "int8":
        return {"bf16_flops": L * (proj + attn) + head, "int8_ops": L * f}
    return {"bf16_flops": L * (proj + attn + f) + head, "int8_ops": 0.0}


def lm_sequence(cfg: dict, start: int, stop: int,
                ffn_dtype: str = "int8") -> Dict[str, float]:
    """Sum of :func:`lm_token` over the tokens at positions
    ``start .. stop - 1`` (position p attends to p + 1 keys)."""
    n = max(stop - start, 0)
    if n == 0:
        return {"bf16_flops": 0.0, "int8_ops": 0.0}
    one = lm_token(cfg, 0, ffn_dtype)
    d, hq, dh, L = cfg["d_model"], cfg["n_heads"], cfg["head_dim"], \
        cfg["n_layers"]
    # sum over p of (p + 1) keys, in closed form
    keys = (start + 1 + stop) * n / 2.0
    return {"bf16_flops": n * one["bf16_flops"]
            + L * 2.0 * 2.0 * hq * dh * keys,
            "int8_ops": n * one["int8_ops"]}

