"""Per-kernel search-space definitions for the autotuner.

Each Pallas kernel exposes a small set of schedule knobs (block/tile sizes,
grid shape by implication). The paper's NNoM kernels are hand-scheduled per
Cortex-M target; the TPU analogue is a per-(kernel, shape, dtype) config
search over these knobs — the AutoTVM recipe from the microtvm-blogpost-eval
reference, shrunk to the handful of parameters our kernels actually expose.

A *config* is a plain dict of kwargs understood by the kernel wrapper
(e.g. ``{"block_co": 64}``). :func:`candidates` enumerates the feasible
configs for a concrete shape signature; :func:`default_config` returns the
hard-coded seed schedule (what the kernels used before this subsystem
existed), which is always feasible and always a member of the space.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Tuple

from repro.kernels.common import (batch_spatial_schedule, contraction_block,
                                  effective_block)

# Kernels the tuner knows about. Names match repro.kernels.ops entry points.
KERNELS = ("conv2d", "depthwise2d", "shift_conv2d", "add_conv2d",
           "causal_conv1d", "matmul", "maxpool2d")

# Conv-grid kernels that take the tiled (block_n, block_h, block_w) schedule
# on top of their channel blocking (the batched/spatially-tiled grid).
_TILED = ("conv2d", "depthwise2d", "shift_conv2d", "add_conv2d", "maxpool2d")

# Hard-coded schedules shipped with the seed kernels (pre-tuner behavior);
# block_n=1 / whole-map spatial tiles are the untiled legacy grid.
_DEFAULTS: Dict[str, Dict[str, int]] = {
    "conv2d": {"block_co": 128, "block_n": 1},
    "depthwise2d": {"block_c": 128, "block_n": 1},
    "shift_conv2d": {"block_co": 128, "block_n": 1},
    "add_conv2d": {"block_co": 128, "block_n": 1},
    "causal_conv1d": {"block_l": 512, "block_c": 512},
    "matmul": {"bm": 256, "bn": 256, "bk": 512},
    "maxpool2d": {"block_c": 128, "block_n": 1},
}

_POW2_BLOCKS = (8, 16, 32, 64, 128, 256)
_MM_BLOCKS = (128, 256, 512)

# int8 operands are 4x smaller than f32, so schedules that would overflow
# VMEM at f32 are feasible quantized — the int8 spaces extend the block
# ranges upward (the paper's SIMD build likewise unlocks wider tiles via
# 4-way byte packing in 32-bit words).
_POW2_BLOCKS_INT8 = _POW2_BLOCKS + (512,)
_MM_BLOCKS_INT8 = _MM_BLOCKS + (1024,)


def _int8(dtype: str) -> bool:
    # "w4a8" = nibble-packed weights, int8 activations: same lane widths /
    # block feasibility as int8, so it shares the int8 candidate space (the
    # cost model, not the space, sees the halved weight bytes)
    return str(dtype) in ("int8", "uint8", "w4a8")


@dataclasses.dataclass(frozen=True)
class ShapeSig:
    """Canonical shape signature of one kernel invocation.

    ``dims`` is a tuple of named ints in kernel-specific order; it is what the
    cache keys on and what the space enumerates against.
    """

    kernel: str
    dims: Tuple[Tuple[str, int], ...]

    def __post_init__(self):
        if self.kernel not in KERNELS:
            raise ValueError(f"unknown kernel {self.kernel!r}; "
                             f"known: {KERNELS}")

    def get(self, name: str) -> int:
        for k, v in self.dims:
            if k == name:
                return v
        raise KeyError(name)

    def key(self) -> str:
        return "_".join(f"{k}{v}" for k, v in self.dims)


def sig_conv2d(n, h, w, cx, cy, hk, groups=1) -> ShapeSig:
    return ShapeSig("conv2d", (("n", n), ("h", h), ("w", w), ("ci", cx),
                               ("co", cy), ("k", hk), ("g", groups)))


def sig_depthwise2d(n, h, w, c, hk) -> ShapeSig:
    return ShapeSig("depthwise2d", (("n", n), ("h", h), ("w", w), ("c", c),
                                    ("k", hk)))


def sig_shift_conv2d(n, h, w, c, cy) -> ShapeSig:
    return ShapeSig("shift_conv2d", (("n", n), ("h", h), ("w", w), ("c", c),
                                     ("co", cy)))


def sig_add_conv2d(n, h, w, cx, cy, hk) -> ShapeSig:
    return ShapeSig("add_conv2d", (("n", n), ("h", h), ("w", w), ("ci", cx),
                                   ("co", cy), ("k", hk)))


def sig_causal_conv1d(b, l, d, k) -> ShapeSig:
    return ShapeSig("causal_conv1d", (("b", b), ("l", l), ("d", d), ("k", k)))


def sig_matmul(m, k, n) -> ShapeSig:
    return ShapeSig("matmul", (("m", m), ("k", k), ("n", n)))


def sig_maxpool2d(n, h, w, c, window, stride) -> ShapeSig:
    return ShapeSig("maxpool2d", (("n", n), ("h", h), ("w", w), ("c", c),
                                  ("k", window), ("s", stride)))


def default_config(kernel: str) -> Dict[str, int]:
    if kernel not in _DEFAULTS:
        raise ValueError(f"unknown kernel {kernel!r}")
    return dict(_DEFAULTS[kernel])


def _out_hw(sig: ShapeSig) -> Tuple[int, int]:
    """Output spatial extent the (block_h, block_w) tiles grid over: the
    input map for the stride-1 SAME conv kernels, the pooled map for
    maxpool2d."""
    h, w = sig.get("h"), sig.get("w")
    if sig.kernel == "maxpool2d":
        win, s = sig.get("k"), sig.get("s")
        return (h - win) // s + 1, (w - win) // s + 1
    return h, w


def _bs_effective(sig: ShapeSig, cfg: Dict[str, int]) -> Dict[str, int]:
    """Effective (block_n, block_h, block_w) half of a tiled-grid schedule
    — resolved by the SAME ``batch_spatial_schedule`` the kernels run."""
    h, w = _out_hw(sig)
    bn, bh, bw, _, _ = batch_spatial_schedule(
        sig.get("n"), h, w, cfg.get("block_n", 1),
        cfg.get("block_h"), cfg.get("block_w"))
    return {"block_n": bn, "block_h": bh, "block_w": bw}


def effective_config(sig: ShapeSig, cfg: Dict[str, int]) -> Dict[str, int]:
    """The schedule the kernel actually runs for ``cfg`` on this shape.

    Divisor-gridded kernels degrade blocks via ``effective_block`` (and the
    tiled-grid kernels resolve block_n/block_h/block_w through
    ``batch_spatial_schedule``); matmul's cdiv M/N grid only clamps to the
    dimension, and its K block is ``contraction_block`` (tiles K exactly).
    Two configs with equal effective schedules are the same compiled
    kernel — the space dedupes on this, and tuned-vs-default
    comparisons are only meaningful across distinct effective schedules.
    """
    k = sig.kernel
    d = default_config(k)

    def get(name):
        return int(cfg.get(name, d[name]))

    if k == "conv2d":
        co_per_g = sig.get("co") // max(sig.get("g"), 1)
        return {"block_co": effective_block(co_per_g, get("block_co")),
                **_bs_effective(sig, cfg)}
    if k == "depthwise2d":
        return {"block_c": effective_block(sig.get("c"), get("block_c")),
                **_bs_effective(sig, cfg)}
    if k == "shift_conv2d":
        return {"block_co": effective_block(sig.get("co"), get("block_co")),
                **_bs_effective(sig, cfg)}
    if k == "add_conv2d":
        return {"block_co": effective_block(sig.get("co"), get("block_co")),
                **_bs_effective(sig, cfg)}
    if k == "maxpool2d":
        return {"block_c": effective_block(sig.get("c"), get("block_c")),
                **_bs_effective(sig, cfg)}
    if k == "causal_conv1d":
        return {"block_l": effective_block(sig.get("l"), get("block_l")),
                "block_c": effective_block(sig.get("d"), get("block_c"))}
    if k == "matmul":
        return {"bm": min(get("bm"), sig.get("m")),
                "bn": min(get("bn"), sig.get("n")),
                "bk": contraction_block(sig.get("k"), get("bk"))}
    raise AssertionError(k)  # pragma: no cover - ShapeSig guards kernel


def _bs_variants(sig: ShapeSig) -> List[Dict[str, int]]:
    """(block_n, block_h, block_w) variants for the tiled-grid kernels,
    feasibility-gated on the shape: batch blocks up to the batch size
    (weight reuse), row/tile blocks only when the map is big enough for the
    halo duplication to buy VMEM headroom. The empty dict is the untiled
    legacy schedule; infeasible variants alias it and dedupe away."""
    n = sig.get("n")
    h, w = _out_hw(sig)
    outs: List[Dict[str, int]] = [{}]
    for bn in (2, 4, 8):
        if bn <= n:
            outs.append({"block_n": bn})
    for bh in (8, 16):
        if bh < h:
            outs.append({"block_h": bh})
    if h > 8 and w > 8:
        outs.append({"block_h": 8, "block_w": 8})
    for bn in (4, 8):
        if bn <= n and h > 8:
            outs.append({"block_n": bn, "block_h": 8})
    return outs


def candidates(sig: ShapeSig, dtype: str = "float32") -> Iterator[Dict[str, int]]:
    """Enumerate feasible configs for one shape, default first.

    Deduped by *effective* schedule, so the default's entry represents its
    whole equivalence class and no other candidate aliases it. ``dtype``
    widens the block ranges for int8 operands (4x smaller footprint). The
    tiled-grid kernels additionally sweep (block_n, block_h, block_w)
    variants on top of their default channel blocking.
    """
    from repro.check.footprint import check_schedule

    k = sig.kernel
    seen = set()
    out: List[Dict[str, int]] = []
    pow2 = _POW2_BLOCKS_INT8 if _int8(dtype) else _POW2_BLOCKS
    mm = _MM_BLOCKS_INT8 if _int8(dtype) else _MM_BLOCKS

    def emit(cfg: Dict[str, int], prune: bool = True):
        key = tuple(sorted(effective_config(sig, cfg).items()))
        if key in seen:
            return
        # static feasibility gate: a schedule the hard verifier rejects is
        # never measured (the soft VMEM_PENALTY only ranked it last before)
        if prune and not check_schedule(sig, cfg, dtype).ok:
            return
        seen.add(key)
        out.append(cfg)

    # the default seed schedule is always a member — it is the fallback the
    # kernels ran before the tuner existed, so the space is never empty
    emit(default_config(k), prune=False)

    if k == "conv2d":
        for bco in pow2:
            emit({"block_co": bco})
    elif k == "depthwise2d":
        for bc in pow2:
            emit({"block_c": bc})
    elif k == "shift_conv2d":
        for bco in pow2:
            emit({"block_co": bco})
    elif k == "add_conv2d":
        for bco in (1, 2, 4, 8, 16, 32) + ((64,) if _int8(dtype) else ()):
            emit({"block_co": bco})
    elif k == "maxpool2d":
        for bc in (32, 64, 128, 256) + ((512,) if _int8(dtype) else ()):
            emit({"block_c": bc})
    elif k == "causal_conv1d":
        for bl in (128, 256, 512, 1024):
            for bc in (128, 256, 512):
                emit({"block_l": bl, "block_c": bc})
    elif k == "matmul":
        for bm in mm:
            for bn in mm:
                for bk in mm:
                    emit({"bm": bm, "bn": bn, "bk": bk})
    else:  # pragma: no cover - KERNELS guard above
        raise AssertionError(k)

    if k in _TILED:
        for var in _bs_variants(sig):
            if var:
                emit(var)

    return iter(out)


def space_size(sig: ShapeSig, dtype: str = "float32") -> int:
    return sum(1 for _ in candidates(sig, dtype))
