"""Authoritative per-kernel VMEM footprint model + hard feasibility verdicts.

One function per concern, shared by every consumer so the soft cost model
and the hard verifier can never disagree:

* :func:`kernel_footprint` — resident VMEM bytes of one grid step of a
  kernel under a schedule, term by term, from the REAL BlockSpec shapes the
  kernels build (halo-padded image tiles, W4 half-width packed weight
  blocks, int32 accumulator scratch, matmul batch folding via the folded M
  extent in the signature). This replaces the six hand-written ``vmem =``
  formulas that used to live in ``tune/runner.py``.
* :func:`check_schedule` — the hard feasibility verdict the executor, the
  dispatch layer, and the cache audit enforce: unknown/invalid schedule
  keys, a block that breaks the TPU tiling rule, and a footprint over the
  chip's VMEM budget are errors; a schedule that silently degrades
  (requested != effective) is a warning.
* :func:`audit_cache` — re-verify every entry of a persistent tune cache
  (``scripts/check_plan.py`` runs it over ``artifacts/tune_cache.json`` in
  CI), flagging stale infeasible entries.

The model prices what is resident in VMEM during one grid step; inter-step
traffic is the cost model's business (``tune.runner.estimate_s``).
"""
from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from repro.core.energy import target_chip
from repro.kernels.common import MXU, SUBLANE, cdiv, round_up

# Schedule keys each kernel's wrapper understands — anything else in a
# config dict is a typo'd knob that would be silently ignored at dispatch.
KNOWN_KEYS: Dict[str, Tuple[str, ...]] = {
    "conv2d": ("block_co", "block_n", "block_h", "block_w"),
    "depthwise2d": ("block_c", "block_n", "block_h", "block_w"),
    "shift_conv2d": ("block_co", "block_n", "block_h", "block_w"),
    "add_conv2d": ("block_co", "block_n", "block_h", "block_w"),
    "maxpool2d": ("block_c", "block_n", "block_h", "block_w"),
    "causal_conv1d": ("block_l", "block_c"),
    "matmul": ("bm", "bn", "bk"),
}

ACC_BYTES = 4                     # int32 / f32 accumulator width


def element_bytes(dtype: str) -> int:
    """Bytes per *activation* element. "w4a8" activations are int8; the
    nibble-packed weight side is priced by :func:`weight_block_bytes`."""
    return {"int8": 1, "uint8": 1, "w4a8": 1,
            "bfloat16": 2, "float16": 2}.get(str(dtype), 4)


def weight_bytes(dtype: str) -> float:
    """Average bytes per *weight* element: 0.5 for nibble-packed W4 (two
    int4 codes per byte), else the element width. The continuous value the
    cost model prices HBM traffic with."""
    return 0.5 if str(dtype) == "w4a8" else float(element_bytes(dtype))


def weight_block_bytes(n_elems_packed_axis: int, n_rest: int,
                       dtype: str) -> int:
    """Exact VMEM bytes of one weight block: W4 packs two codes per byte
    along its unpack axis (``ceil(n/2)`` bytes, the half-width BlockSpec the
    kernels declare), everything else is ``n * element_bytes``."""
    if str(dtype) == "w4a8":
        return cdiv(n_elems_packed_axis, 2) * n_rest       # int8 bytes
    return n_elems_packed_axis * n_rest * element_bytes(dtype)


def vmem_budget() -> int:
    """VMEM budget in bytes of the chip the kernels are scheduled for
    (``core.energy.target_chip``: v5e, also on the CPU where interpret mode
    validates v5e feasibility; it raises on a TPU of another kind).
    REPRO_VMEM_BUDGET overrides it (e.g. to model a smaller part, the
    paper's Cortex-M framing)."""
    env = os.environ.get("REPRO_VMEM_BUDGET")
    if env:
        return int(env)
    return target_chip().vmem_bytes


@dataclasses.dataclass(frozen=True)
class Footprint:
    """Resident VMEM bytes of one grid step, term by term."""

    kernel: str
    terms: Tuple[Tuple[str, int], ...]

    @property
    def total_bytes(self) -> int:
        return sum(v for _, v in self.terms)

    def breakdown(self) -> str:
        return " + ".join(f"{k}={v}" for k, v in self.terms)


def _fp(kernel: str, **terms: int) -> Footprint:
    return Footprint(kernel, tuple((k, int(v)) for k, v in terms.items()))


def kernel_footprint(sig, config: Optional[dict] = None,
                     dtype: str = "int8") -> Footprint:
    """VMEM footprint of one grid step of ``sig.kernel`` under ``config``.

    ``config`` is resolved through ``tune.space.effective_config`` first
    (idempotent), so the footprint describes the schedule the kernel
    actually runs. Terms mirror the kernels' BlockSpecs:

    - ``img``: the halo-padded input tile block (the tiled conv/pool grids
      duplicate ``size - step`` halo rows at wrapper level, so the block is
      ``(bn, bh + hk - 1, bw + hk - 1, C)``);
    - ``wts``: the weight block — HALF width for W4 nibble-packed weights
      (only packed bytes cross HBM -> VMEM);
    - ``out``: the output block at the activation width;
    - ``acc``: int32 accumulator scratch (the add-conv |x-w| broadcast
      intermediate is its dominating instance).

    Matmul batch folding: ``CompiledPlan``/``matmul_q8`` fold a leading
    batch dim into M before building the grid, so a batched matmul's
    signature already carries the folded ``m = batch * rows`` and no extra
    term is needed here.
    """
    from repro.tune.space import _out_hw, effective_config

    k = sig.kernel
    eff = effective_config(sig, config or {})
    eb = element_bytes(dtype)

    if k == "conv2d":
        ci, hk, g = sig.get("ci"), sig.get("k"), max(sig.get("g"), 1)
        cxg = ci // g
        bco = eff["block_co"]
        bn, bh, bw = eff["block_n"], eff["block_h"], eff["block_w"]
        halo = hk - 1
        return _fp(
            k,
            img=bn * (bh + halo) * (bw + halo) * cxg * eb,
            wts=hk * hk * weight_block_bytes(cxg, bco, dtype),
            out=bn * bh * bw * bco * eb,
            acc=bn * bh * bw * bco * ACC_BYTES,
        )

    if k == "depthwise2d":
        hk = sig.get("k")
        bc = eff["block_c"]
        bn, bh, bw = eff["block_n"], eff["block_h"], eff["block_w"]
        halo = hk - 1
        return _fp(
            k,
            img=bn * (bh + halo) * (bw + halo) * bc * eb,
            wts=weight_block_bytes(hk, hk * bc, dtype),   # W4 packs tap rows
            out=bn * bh * bw * bc * eb,
            acc=bn * bh * bw * bc * ACC_BYTES,
        )

    if k == "shift_conv2d":
        c = sig.get("c")
        bco = eff["block_co"]
        bn, bh, bw = eff["block_n"], eff["block_h"], eff["block_w"]
        # the shift gather reads every input channel per step; halo = 2*pad
        # with pad = kernel_size // 2 (3x3 shift grid -> pad 1, the only
        # configuration the paper's shift-conv uses; the signature carries
        # no kernel extent)
        pad = 1
        return _fp(
            k,
            img=bn * (bh + 2 * pad) * (bw + 2 * pad) * c * eb,
            wts=weight_block_bytes(c, bco, dtype),
            out=bn * bh * bw * bco * eb,
            acc=bn * bh * bw * bco * ACC_BYTES,
        )

    if k == "add_conv2d":
        ci, hk = sig.get("ci"), sig.get("k")
        bco = eff["block_co"]
        bn, bh, bw = eff["block_n"], eff["block_h"], eff["block_w"]
        halo = hk - 1
        rows = bn * bh * bw
        return _fp(
            k,
            img=bn * (bh + halo) * (bw + halo) * ci * eb,
            wts=hk * hk * weight_block_bytes(ci, bco, dtype),
            out=rows * bco * eb,
            # |x - w| broadcast: the (rows, Cx, BCO) intermediate is the VMEM
            # hog the spatial tile exists to bound. Each (Cx, BCO) slab
            # occupies whole (8, 128) vreg tiles, and the difference and its
            # absolute value are live together
            acc=(2 * rows * round_up(ci, SUBLANE) * round_up(bco, MXU)
                 + rows * bco) * ACC_BYTES,
        )

    if k == "maxpool2d":
        win, s = sig.get("k"), sig.get("s")
        bc = eff["block_c"]
        bn, bh, bw = eff["block_n"], eff["block_h"], eff["block_w"]
        nph, halo = min(win, s), (win - 1) // s    # stride-phase tiles
        return _fp(
            k,
            img=bn * nph * nph * (bh + halo) * (bw + halo) * bc * eb,
            out=bn * bh * bw * bc * eb,
        )

    if k == "causal_conv1d":
        kk = sig.get("k")
        bl, bc = eff["block_l"], eff["block_c"]
        return _fp(
            k,
            # current + lookahead block of the same padded array (the
            # causal-halo trick: two BlockSpecs over one input)
            img=2 * bl * bc * eb,
            wts=kk * bc * eb,
            out=bl * bc * eb,
            acc=bl * bc * ACC_BYTES,
        )

    if k == "matmul":
        bm, bn_, bk = eff["bm"], eff["bn"], eff["bk"]
        return _fp(
            k,
            a=bm * bk * eb,
            b=weight_block_bytes(bk, bn_, dtype),
            out=bm * bn_ * eb,
            acc=bm * bn_ * ACC_BYTES,        # pltpu.VMEM scratch accumulator
        )

    raise ValueError(f"unknown kernel {k!r}")


# --------------------------------------------------------------------------
# Hard feasibility verdict
# --------------------------------------------------------------------------

def tiling_errors(sig, eff: dict) -> List[str]:
    """The TPU block-shape rule on an effective schedule: the last two dims
    of every block are multiples of (8, 128) or span the whole array axis.
    The kernels' layouts put channels (or matmul N/K) on the 128-lane axis
    and the output tile width (or matmul M) on the 8-sublane axis, so the
    rule reduces to one (block, extent, multiple) triple per knob.
    ``causal_conv1d`` is not covered (off the serving paths)."""
    from repro.tune.space import _out_hw

    k = sig.kernel
    rules = []                       # (knob, block, full extent, multiple)
    if k in ("conv2d", "depthwise2d", "shift_conv2d", "add_conv2d",
             "maxpool2d"):
        if k == "conv2d":
            knob, full = "block_co", sig.get("co") // max(sig.get("g"), 1)
        elif k in ("shift_conv2d", "add_conv2d"):
            knob, full = "block_co", sig.get("co")
        else:
            knob, full = "block_c", sig.get("c")
        rules.append((knob, eff[knob], full, MXU))
        rules.append(("block_w", eff["block_w"], _out_hw(sig)[1], SUBLANE))
    elif k == "matmul":
        rules += [("bm", eff["bm"], sig.get("m"), SUBLANE),
                  ("bn", eff["bn"], sig.get("n"), MXU),
                  ("bk", eff["bk"], sig.get("k"), MXU)]
    return [f"{knob}={b} breaks the TPU tiling rule: a multiple of {mult} "
            f"or the whole extent {full}"
            for knob, b, full, mult in rules if b != full and b % mult]


@dataclasses.dataclass
class Verdict:
    """Result of one :func:`check_schedule` call."""

    ok: bool
    sig_key: str
    kernel: str
    dtype: str
    config: dict
    effective: dict
    footprint: Optional[Footprint]
    budget: int
    errors: List[str]
    warnings: List[str]

    def message(self) -> str:
        head = f"{self.kernel}/{self.sig_key} [{self.dtype}] {self.config}"
        if self.ok and not self.warnings:
            return f"{head}: ok"
        tail = "; ".join(self.errors + self.warnings)
        return f"{head}: {tail}"


def check_schedule(sig, config: Optional[dict], dtype: str = "int8", *,
                   budget: Optional[int] = None) -> Verdict:
    """Static feasibility verdict for one (kernel, shape, schedule, dtype).

    Errors (``ok=False``): unknown schedule keys, non-positive block values,
    a block that breaks the TPU tiling rule (:func:`tiling_errors`), VMEM
    footprint over the chip's VMEM budget. Warnings: a requested block
    the kernel silently degrades (requested != effective schedule) —
    legal, but the measured entry then describes a different schedule than
    its config dict suggests.
    """
    from repro.tune.space import effective_config

    config = dict(config or {})
    dtype = str(dtype)
    budget = vmem_budget() if budget is None else int(budget)
    errors: List[str] = []
    warnings: List[str] = []

    known = KNOWN_KEYS.get(sig.kernel, ())
    unknown = sorted(set(config) - set(known))
    if unknown:
        errors.append(f"unknown schedule key(s) {unknown}; "
                      f"{sig.kernel} understands {sorted(known)}")
    bad = {k: v for k, v in config.items()
           if k in known and (not isinstance(v, int) or v < 1)}
    if bad:
        errors.append(f"non-positive/non-int block value(s) {bad}")

    eff: dict = {}
    fp: Optional[Footprint] = None
    if not errors:
        eff = effective_config(sig, config)
        errors.extend(tiling_errors(sig, eff))
        fp = kernel_footprint(sig, eff, dtype)
        if fp.total_bytes > budget:
            errors.append(
                f"VMEM footprint {fp.total_bytes} B exceeds the "
                f"{budget} B budget ({fp.breakdown()}); shrink "
                f"block_n/block_h/block_w or the channel block")
        degraded = {k: (v, eff[k]) for k, v in config.items()
                    if k in eff and eff[k] != v}
        if degraded:
            warnings.append(
                "requested schedule degrades on this shape: "
                + ", ".join(f"{k}: {a} -> {b}"
                            for k, (a, b) in degraded.items()))

    return Verdict(ok=not errors, sig_key=sig.key(), kernel=sig.kernel,
                   dtype=dtype, config=config, effective=eff, footprint=fp,
                   budget=budget, errors=errors, warnings=warnings)


# --------------------------------------------------------------------------
# Tune-cache audit
# --------------------------------------------------------------------------

_DIM_RE = re.compile(r"([a-z]+)(\d+)")


def parse_cache_key(key: str):
    """Invert ``tune.cache.cache_key``: ``kernel|shape|dtype|backend`` ->
    ``(ShapeSig, dtype, backend)``. The shape key is the underscore-joined
    ``<name><int>`` dims in signature order."""
    from repro.tune.space import ShapeSig
    kernel, shape_key, dtype, backend = key.split("|")
    dims = tuple((m.group(1), int(m.group(2)))
                 for m in _DIM_RE.finditer(shape_key))
    sig = ShapeSig(kernel, dims)
    if sig.key() != shape_key:
        raise ValueError(f"unparseable shape key {shape_key!r} in {key!r}")
    return sig, dtype, backend


def audit_cache(cache=None, *, budget: Optional[int] = None) -> List[dict]:
    """Re-verify every entry of a persistent tune cache against the current
    footprint model; one row per entry. Stale infeasible entries (tuned
    before the verifier existed, or against a larger budget) come back with
    ``ok=False`` and the verdict's reasons — re-tune or drop them.

    ``cache`` is a ``tune.cache.TuneCache``, a path, or None for the
    default committed cache.
    """
    from repro.tune import cache as _cache
    if cache is None or isinstance(cache, str):
        cache = _cache.TuneCache(cache or _cache.default_cache_path())
    rows = []
    for key in sorted(cache.entries):
        entry = cache.entries[key]
        sig, dtype, _ = parse_cache_key(key)
        v = check_schedule(sig, entry.get("config") or {}, dtype,
                           budget=budget)
        # a cached config larger than the shape is deterministic clamping
        # (candidates() dedupes by effective schedule) — informational,
        # not a hazard, so it lands in "notes" rather than "warnings"
        notes = [w for w in v.warnings if "degrades" in w]
        warns = [w for w in v.warnings if w not in notes]
        rows.append({
            "key": key, "ok": v.ok, "config": dict(entry.get("config") or {}),
            "effective": v.effective, "source": entry.get("source"),
            "vmem_bytes": v.footprint.total_bytes if v.footprint else None,
            "budget_bytes": v.budget,
            "errors": v.errors, "warnings": warns, "notes": notes,
        })
    return rows


def summarize_audit(rows: Iterable[dict]) -> dict:
    rows = list(rows)
    return {
        "entries": len(rows),
        "feasible": sum(r["ok"] for r in rows),
        "infeasible": [r["key"] for r in rows if not r["ok"]],
        "warnings": sum(bool(r["warnings"]) for r in rows),
        "notes": sum(bool(r.get("notes")) for r in rows),
    }
