"""Bytes the expert layer's kernel (``moe_experts``) has to move, from the
engine's expert counters, with no padding and no tiling: what the routing
needs, not what a schedule happens to do.

Each expert given at least one token in a call has its three (ff, d)
matrices read once; each (token, expert) assignment reads its token's
activations and writes its result row (float32). The kernel moves at least
these bytes (padding rows and re-read tiles only add), so a roofline share
built on them cannot pass 100%.
"""
from __future__ import annotations


def expert_bytes(experts_hit: float, assignments: float, d_model: int,
                 expert_ff: int, weight_bytes: int = 2, act_bytes: int = 2,
                 out_bytes: int = 4) -> float:
    """Bytes for ``experts_hit`` expert reads (summed over calls) and
    ``assignments`` routed rows, bf16 weights and activations by default."""
    return (experts_hit * 3.0 * d_model * expert_ff * weight_bytes
            + assignments * float(d_model) * (act_bytes + out_bytes))
