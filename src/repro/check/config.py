"""Serve-config static checks: enum/range validation, the combinations of
model family, scheduler, cache layout and precision the engine serves, and
the KV-cache budget, computed before any parameter is touched.

:func:`unsupported` is THE rule for which combinations the engine serves:
``Engine.__init__`` raises ``NotImplementedError`` on what it returns, and
:func:`check_serve_config` returns it with every other violation at once
(CI and ``scripts/check_plan.py`` want the full list, not the first raise),
plus the numeric checks: positive batch/length/bucket knobs and the
resident KV budget ``kv_cache_bytes`` against an optional device budget.
"""
from __future__ import annotations

from typing import List, Optional

SCHEDULERS = ("continuous", "static")
SHED_POLICIES = ("reject", "drop")
PRECISIONS = ("float", "int8", "int8-xla", "w4a8")
KV_CACHES = ("float", "int8")
KV_LAYOUTS = ("contiguous", "paged")

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def paged_num_blocks(scfg) -> int:
    """Resolved pool size: ``kv_num_blocks`` when set, else the contiguous
    capacity equivalent — ``max_batch * (max_len // block_size)`` usable
    pages plus the reserved garbage page 0."""
    if getattr(scfg, "kv_num_blocks", None):
        return scfg.kv_num_blocks
    return scfg.max_batch * (scfg.max_len // scfg.kv_block_size) + 1


def kv_cache_bytes(cfg, scfg) -> int:
    """Resident KV budget of the ONE live decode cache.

    Contiguous layout: ``layers * K&V * max_batch * max_len * n_kv_heads *
    head_dim * width`` (int8 kv adds the per-(position, head) f32 scale
    sideband). Paged layout: ``layers * K&V * num_blocks * block_size``
    positions instead — memory scales with the pool, not ``max_batch *
    max_len`` — plus the (max_batch, max_len / block_size) int32 block
    table."""
    width = 1 if scfg.kv_cache == "int8" else \
        _DTYPE_BYTES.get(cfg.compute_dtype, 4)
    layers = cfg.n_attn_layers
    per_pos = cfg.n_kv_heads * cfg.head_dim * width
    if scfg.kv_cache == "int8":
        per_pos += cfg.n_kv_heads * 4           # f32 scale per (pos, head)
    if getattr(scfg, "kv_layout", "contiguous") == "paged":
        positions = paged_num_blocks(scfg) * scfg.kv_block_size
        table = 4 * scfg.max_batch * (scfg.max_len // scfg.kv_block_size)
        return layers * 2 * positions * per_pos + table
    return layers * 2 * scfg.max_batch * scfg.max_len * per_pos


def unsupported(scfg, cfg=None) -> List[str]:
    """Every combination of scheduler, cache, layout, precision and model
    the engine does not serve (values the enums do not know are left to
    :func:`check_serve_config`)."""
    errs: List[str] = []
    static = scfg.scheduler == "static"
    paged = getattr(scfg, "kv_layout", "contiguous") == "paged"
    if scfg.kv_cache == "int8" and static:
        errs.append("kv_cache='int8' needs scheduler='continuous' (the "
                    "static path decodes off the float prefill cache)")
    if paged and static:
        errs.append("kv_layout='paged' needs scheduler='continuous' "
                    "(the static path decodes off the prefill cache)")
    if cfg is None:
        return errs
    recurrent = cfg.family in ("ssm", "hybrid", "encdec")
    if cfg.family == "encdec" and scfg.scheduler == "continuous":
        errs.append("continuous batching needs slotted caches; encdec "
                    "is not slotted — use scheduler='static'")
    if paged and recurrent:
        errs.append("kv_layout='paged' covers attention-family dense "
                    "KV caches only (no ssm / hybrid / encdec)")
    if scfg.precision in PRECISIONS[1:] and (recurrent
                                             or cfg.moe is not None):
        errs.append(f"precision={scfg.precision!r} quantizes dense FFN "
                    "matmuls; moe/ssm/hybrid/encdec are unsupported")
    if scfg.kv_cache == "int8" and (recurrent or cfg.layer_types):
        errs.append("kv_cache='int8' covers attention-family dense KV "
                    "caches only (no ssm / hybrid / encdec / short "
                    "convolutions)")
    if cfg.layer_types and not paged:
        errs.append("a pattern stack (layer_types) serves through "
                    "kv_layout='paged' and scheduler='continuous' only")
    if paged and cfg.layer_types and cfg.n_conv_layers \
            and getattr(scfg, "prefix_cache", False):
        errs.append("prefix_cache=True with short-convolution layers: a "
                    "prefix hit would need the conv state at the page "
                    "boundary, which is not kept — set prefix_cache=False")
    return errs


def _check_resilience(scfg, errs: List[str]):
    """Failure-model knobs shared by ServeConfig and CNNServeConfig:
    deadline_s / max_queue / shed_policy / max_retries / retry_backoff_s
    (EXPERIMENTS.md §Resilience)."""
    d = getattr(scfg, "deadline_s", None)
    if d is not None and (not isinstance(d, (int, float)) or d <= 0):
        errs.append(f"deadline_s must be > 0 (or None to disable), "
                    f"got {d!r}")
    mq = getattr(scfg, "max_queue", None)
    if mq is not None:
        if not isinstance(mq, int) or mq < 1:
            errs.append(f"max_queue must be a positive int (or None to "
                        f"disable shedding), got {mq!r}")
        elif isinstance(scfg.max_batch, int) and mq < scfg.max_batch:
            errs.append(
                f"max_queue={mq} is below max_batch={scfg.max_batch}: the "
                "scheduler could never fill a round before shedding — "
                "raise max_queue to at least max_batch")
    sp = getattr(scfg, "shed_policy", "reject")
    if sp not in SHED_POLICIES:
        errs.append(f"unknown shed_policy: {sp!r} "
                    f"(choose from {SHED_POLICIES})")
    mr = getattr(scfg, "max_retries", 0)
    if not isinstance(mr, int) or mr < 0:
        errs.append(f"max_retries must be an int >= 0, got {mr!r}")
    rb = getattr(scfg, "retry_backoff_s", 0.0)
    if not isinstance(rb, (int, float)) or rb < 0:
        errs.append(f"retry_backoff_s must be >= 0, got {rb!r}")


def check_serve_config(scfg, cfg=None, *, hbm_budget: Optional[int] = None,
                       strict: bool = True) -> List[str]:
    """Every violation of a :class:`~repro.serve.engine.ServeConfig`
    (optionally against a :class:`~repro.configs.base.ModelConfig`).
    Empty list == the config constructs and fits.

    ``strict=False`` is the constructor-grade subset ``Engine.__init__``
    enforces; strict mode (the CLI/CI default) additionally flags configs
    that only fail later at submit time (a prefill bucket floor no prompt
    can fit under the per-slot KV cap)."""
    errs: List[str] = []
    if scfg.scheduler not in SCHEDULERS:
        errs.append(f"unknown scheduler: {scfg.scheduler!r} "
                    f"(choose from {SCHEDULERS})")
    if scfg.precision not in PRECISIONS:
        errs.append(f"unknown precision: {scfg.precision!r} "
                    f"(choose from {PRECISIONS})")
    if scfg.kv_cache not in KV_CACHES:
        errs.append(f"unknown kv_cache: {scfg.kv_cache!r} "
                    f"(choose from {KV_CACHES})")
    for knob in ("max_batch", "max_len", "prefill_bucket"):
        v = getattr(scfg, knob)
        if not isinstance(v, int) or v < 1:
            errs.append(f"{knob} must be a positive int, got {v!r}")
    _check_resilience(scfg, errs)
    errs += unsupported(scfg, cfg)

    layout = getattr(scfg, "kv_layout", "contiguous")
    if layout not in KV_LAYOUTS:
        errs.append(f"unknown kv_layout: {layout!r} "
                    f"(choose from {KV_LAYOUTS})")
    elif layout == "paged":
        bs = scfg.kv_block_size
        if not isinstance(bs, int) or bs < 1:
            errs.append(f"kv_block_size must be a positive int, got {bs!r}")
        elif isinstance(scfg.max_len, int) and scfg.max_len % bs:
            errs.append(f"kv_block_size={bs} must divide max_len="
                        f"{scfg.max_len}: a slot's block table must span "
                        "exactly max_len positions")
        nb = scfg.kv_num_blocks
        if nb is not None and (not isinstance(nb, int) or nb < 2):
            errs.append(f"kv_num_blocks must be an int >= 2 (page 0 is the "
                        f"reserved garbage page), got {nb!r}")
        elif isinstance(bs, int) and bs >= 1 \
                and isinstance(scfg.max_len, int) \
                and not scfg.max_len % bs:
            usable = paged_num_blocks(scfg) - 1
            if usable < scfg.max_len // bs:
                errs.append(
                    f"kv_num_blocks={nb} leaves {usable} usable pages, "
                    f"fewer than the {scfg.max_len // bs} one request at "
                    f"max_len={scfg.max_len} needs — the engine could "
                    "deadlock growing a lone sequence")
            elif strict and usable < scfg.max_batch:
                errs.append(
                    f"kv_num_blocks={nb} leaves {usable} usable pages, "
                    f"fewer than max_batch={scfg.max_batch} minimum-length "
                    "requests (one page each) — slots can never all fill")

    if cfg is not None:
        if strict and not cfg.sub_quadratic() and cfg.family != "encdec" \
                and scfg.prefill_bucket > scfg.max_len:
            errs.append(f"prefill_bucket={scfg.prefill_bucket} exceeds "
                        f"max_len={scfg.max_len}; every bucket would "
                        "overflow the per-slot KV capacity")
        if hbm_budget is not None and cfg.family not in ("ssm",):
            kv = kv_cache_bytes(cfg, scfg)
            if kv > hbm_budget:
                errs.append(
                    f"resident KV cache needs {kv / 2**20:.1f} MiB "
                    f"({cfg.n_attn_layers} layers x 2 x {scfg.max_batch} slots "
                    f"x {scfg.max_len} positions), over the "
                    f"{hbm_budget / 2**20:.1f} MiB budget — shrink "
                    "max_batch/max_len or use kv_cache='int8'")
    return errs


def check_cnn_serve_config(scfg) -> List[str]:
    """Violations of a :class:`~repro.serve.cnn.CNNServeConfig`."""
    errs: List[str] = []
    if not isinstance(scfg.max_batch, int) or scfg.max_batch < 1:
        errs.append(f"max_batch must be a positive int, got "
                    f"{scfg.max_batch!r}")
    _check_resilience(scfg, errs)
    return errs
