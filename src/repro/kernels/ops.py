"""Public jit'd entry points for the Pallas kernel layer.

``method='pallas'`` runs the TPU kernels (interpret=True automatically off
TPU); ``method='xla'`` runs the pure-jnp oracle (the direct / no-SIMD
baseline). Models and benchmarks call these, never pallas_call directly.

Schedule selection: every Pallas path consults the ``repro.tune`` subsystem
unless an explicit ``config=`` dict is passed — persistent cache entries
(committed by ``scripts/tune.py``) win, otherwise the analytic fallback
cost model picks the schedule. Lookups are memoized in-process, so the
per-call overhead after the first trace is one dict probe. An explicit
``config=`` together with ``method='xla'`` is a contradiction (the oracle
has no schedule knobs) and raises, mirroring ``_check_method``.

Epilogues: the quantized entry points thread ``requant_shift`` (Algorithm-1
round-to-nearest shift) and ``act="relu"`` (fused activation at accumulator
scale, applied before the shift) to both engines, so pallas and xla stay
bit-exact including the fused activation.

Observability: every entry point counts its dispatch into the process
metrics registry as ``kernels.dispatch.<kernel>.<method>`` (pallas vs xla
per primitive — the engine-coverage picture ``scripts/bench_snapshot.py``
snapshots), and ``causal_conv1d``'s auto->xla mesh fallback is counted
separately as ``kernels.fallback.causal_conv1d.mesh``. Calls from inside a
jit count once per trace, eager calls once per call.

Failure model (EXPERIMENTS.md §Resilience): the ``kernels.dispatch`` fault
seam (``repro.faults``) fires once per pallas dispatch (per trace from
inside a jit). An injected raise is retried a bounded number of times;
repeated injected failure degrades THAT kernel to its jnp oracle for the
rest of the process (a real kernel or compile error propagates instead) —
sticky, one warning, counted as ``kernels.degraded.<kernel>`` — which is semantics-preserving because the
oracles are bit-exact with the pallas kernels (tests/test_kernels.py).
``reset_degraded()`` clears the sticky state (tests); the dispatch
counters keep recording the *requested* method, so degraded traffic is
the gap between ``kernels.dispatch.<k>.pallas`` and
``kernels.degraded.<k>``.
"""
from __future__ import annotations

import functools
import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.faults import inject as _faults
from repro.obs import metrics as _obs_metrics

from . import ref
from .common import use_interpret
from .conv_add import add_conv2d as _add_pallas
from .conv_dw import depthwise2d as _dw_pallas
from .conv_im2col import conv2d_im2col as _conv_pallas
from .conv_shift import shift_conv2d as _shift_pallas
from .conv1d_causal import causal_conv1d as _c1d_pallas
from .matmul_q8 import matmul as _mm_pallas
from .pool import maxpool2d as _pool_pallas


def _check_method(method: str, allowed=("pallas", "xla")):
    if method not in allowed:
        raise ValueError(f"unknown method {method!r}; expected one of {allowed}")


def _count_dispatch(kernel: str, method: str):
    _obs_metrics.counter(f"kernels.dispatch.{kernel}.{method}").inc()


# --------------------------------------------------- degradation (resilience)

#: kernels stuck on their xla oracle after repeated pallas failure:
#: kernel name -> repr of the exception that exhausted the retries
_DEGRADED: Dict[str, str] = {}

#: retries per dispatch before a kernel degrades. Kernel dispatch happens
#: at trace time, so there is no backoff sleep — a deterministic failure
#: fails identically on every attempt and degrades immediately after.
_MAX_DISPATCH_RETRIES = 2


def degraded() -> Dict[str, str]:
    """Kernels currently degraded to their oracle (name -> cause)."""
    return dict(_DEGRADED)


def reset_degraded() -> None:
    """Clear the sticky pallas->xla degradations (test isolation)."""
    _DEGRADED.clear()


def _is_degraded(kernel: str) -> bool:
    return kernel in _DEGRADED


def _pallas_guard(kernel: str, pallas_fn, xla_fn):
    """Run ``pallas_fn`` behind the ``kernels.dispatch`` fault seam with
    bounded retries; repeated INJECTED failure degrades ``kernel`` to
    ``xla_fn`` — once, stickily, with one warning. A real compile or kernel
    error propagates: degrading it would serve a broken kernel's traffic
    from the oracle and hide the fault. The schedule lookup /
    explicit-config feasibility check stay OUTSIDE this guard: a
    CheckError is a caller bug, not a transient kernel fault."""
    last: Optional[BaseException] = None
    for _ in range(_MAX_DISPATCH_RETRIES + 1):
        try:
            _faults.check("kernels.dispatch")
            return pallas_fn()
        except _faults.InjectedFault as e:
            last = e                    # transient by construction: retry
    _DEGRADED[kernel] = repr(last)
    _obs_metrics.counter(f"kernels.degraded.{kernel}").inc()
    warnings.warn(
        f"kernel {kernel}: pallas dispatch failed repeatedly ({last!r}); "
        f"degraded to the xla oracle for the rest of the process "
        f"(bit-exact, slower — reset_degraded() to retry pallas)",
        RuntimeWarning, stacklevel=3)
    return xla_fn()


def _check_no_config(method: str, config, *extra_knobs):
    """The xla oracle has no schedule: an explicit config (or explicit block
    knobs) together with method='xla' is a conflicting-arguments error, not
    something to silently ignore."""
    if config is not None or any(k is not None for k in extra_knobs):
        raise ValueError(
            f"method={method!r} runs the jnp oracle, which has no schedule "
            "knobs; drop the explicit config=/block arguments or use "
            "method='pallas'")


def _tuned(sig_fn, *dims, dtype):
    """Cache/analytic schedule lookup; lazy import avoids a module cycle
    (repro.tune.runner measures through these very kernels)."""
    from repro import tune
    return tune.get_config(sig_fn(*dims), str(dtype))


def _check_explicit(sig_fn, *dims, config, dtype):
    """An explicitly-passed schedule gets the same hard feasibility verdict
    the tuner and the executor enforce (``repro.check.check_schedule``) —
    a readable error here beats a Mosaic VMEM failure three layers down.
    Returns the config unchanged."""
    from repro.check import CheckError
    from repro.check.footprint import check_schedule
    v = check_schedule(sig_fn(*dims), config, str(dtype))
    if not v.ok:
        raise CheckError(
            f"infeasible schedule for {v.kernel}/{v.sig_key} "
            f"[{v.dtype}] {v.config}:", v.errors)
    return config


def _w4_dtype(x, w_shifts):
    """Tune-space dtype key: W4-packed weights get their own signature
    dtype ('w4a8') so v2-era int8 cache entries are never misapplied to the
    halved-weight-traffic search space (see tune.cache.SCHEMA_VERSION)."""
    if w_shifts is None:
        return x.dtype
    if x.dtype != jnp.int8:
        raise ValueError("W4 weights require int8 activations (W4A8)")
    return "w4a8"


def conv2d(x, w, bias=None, *, groups: int = 1, method: str = "pallas",
           requant_shift: Optional[int] = None, act: Optional[str] = None,
           config: Optional[dict] = None,
           w_shifts: Optional[jax.Array] = None):
    _check_method(method)
    _count_dispatch("conv2d", method)

    def _xla():
        if w_shifts is not None:
            return ref.conv2d_w4_ref(x, w, w_shifts, bias, groups=groups,
                                     requant_shift=requant_shift, act=act)
        if requant_shift is not None:
            return ref.conv2d_q8_ref(x, w, bias, groups=groups,
                                     requant_shift=requant_shift, act=act)
        return ref.conv2d_ref(x, w, bias, groups=groups, act=act)

    if method == "xla":
        _check_no_config(method, config)
        return _xla()
    if _is_degraded("conv2d"):
        return _xla()
    from repro.tune import sig_conv2d
    n, h, wd, cx = x.shape
    if config is None:
        config = _tuned(sig_conv2d, n, h, wd, cx, w.shape[-1], w.shape[0],
                        groups, dtype=_w4_dtype(x, w_shifts))
    else:
        _check_explicit(sig_conv2d, n, h, wd, cx, w.shape[-1], w.shape[0],
                        groups, config=config, dtype=_w4_dtype(x, w_shifts))
    return _pallas_guard("conv2d", lambda: _conv_pallas(
        x, w, bias, groups=groups, requant_shift=requant_shift,
        act=act, interpret=use_interpret(), config=config,
        w_shifts=w_shifts), _xla)


def depthwise2d(x, w_dw, *, method: str = "pallas",
                requant_shift: Optional[int] = None, act: Optional[str] = None,
                config: Optional[dict] = None,
                w_shifts: Optional[jax.Array] = None):
    _check_method(method)
    _count_dispatch("depthwise2d", method)

    def _xla():
        if w_shifts is not None:
            return ref.depthwise2d_w4_ref(x, w_dw, w_shifts,
                                          requant_shift=requant_shift, act=act)
        if requant_shift is not None:
            return ref.depthwise2d_q8_ref(x, w_dw, requant_shift=requant_shift,
                                          act=act)
        return ref.depthwise2d_ref(x, w_dw, act=act)

    if method == "xla":
        _check_no_config(method, config)
        return _xla()
    if _is_degraded("depthwise2d"):
        return _xla()
    from repro.tune import sig_depthwise2d
    n, h, wd, c = x.shape
    hk = w_dw.shape[1] if w_shifts is not None else w_dw.shape[0]
    if config is None:
        config = _tuned(sig_depthwise2d, n, h, wd, c, hk,
                        dtype=_w4_dtype(x, w_shifts))
    else:
        _check_explicit(sig_depthwise2d, n, h, wd, c, hk,
                        config=config, dtype=_w4_dtype(x, w_shifts))
    return _pallas_guard("depthwise2d", lambda: _dw_pallas(
        x, w_dw, requant_shift=requant_shift, act=act,
        interpret=use_interpret(), config=config, w_shifts=w_shifts), _xla)


def shift_conv2d(x, shifts, w_pw, bias=None, *, method: str = "pallas",
                 requant_shift: Optional[int] = None,
                 act: Optional[str] = None,
                 config: Optional[dict] = None,
                 max_shift: Optional[int] = None,
                 w_shifts: Optional[jax.Array] = None):
    """``max_shift`` bounds |shift| when the table is traced (jit): pass
    ``kernel_size // 2``; unused when the table is concrete. ``bias`` is
    added at accumulator scale (quantized path only)."""
    _check_method(method)
    _count_dispatch("shift_conv2d", method)

    def _xla():
        if w_shifts is not None:
            return ref.shift_conv2d_w4_ref(x, shifts, w_pw, w_shifts, bias,
                                           requant_shift=requant_shift,
                                           max_shift=max_shift, act=act)
        if requant_shift is not None:
            return ref.shift_conv2d_q8_ref(x, shifts, w_pw, bias,
                                           requant_shift=requant_shift,
                                           max_shift=max_shift, act=act)
        if bias is not None:
            raise ValueError("shift_conv2d: bias without requant_shift is "
                             "only supported on the quantized path")
        return ref.shift_conv2d_ref(x, shifts, w_pw, max_shift=max_shift,
                                    act=act)

    if method == "xla":
        _check_no_config(method, config)
        return _xla()
    if _is_degraded("shift_conv2d"):
        return _xla()
    from repro.tune import sig_shift_conv2d
    n, h, wd, c = x.shape
    if config is None:
        config = _tuned(sig_shift_conv2d, n, h, wd, c, w_pw.shape[-1],
                        dtype=_w4_dtype(x, w_shifts))
    else:
        _check_explicit(sig_shift_conv2d, n, h, wd, c, w_pw.shape[-1],
                        config=config, dtype=_w4_dtype(x, w_shifts))
    return _pallas_guard("shift_conv2d", lambda: _shift_pallas(
        x, shifts, w_pw, bias, requant_shift=requant_shift,
        act=act, interpret=use_interpret(), config=config,
        w_shifts=w_shifts), _xla)


def add_conv2d(x, w, bias=None, *, method: str = "pallas",
               requant_shift: Optional[int] = None,
               x_preshift: int = 0, w_preshift: int = 0,
               act: Optional[str] = None,
               config: Optional[dict] = None,
               w_shifts: Optional[jax.Array] = None):
    """``bias`` is added at accumulator scale (quantized path only);
    ``x_preshift``/``w_preshift`` are the Algorithm-1 (right) scale-alignment
    left shifts applied to the operands before |x - w|."""
    _check_method(method)
    _count_dispatch("add_conv2d", method)

    def _xla():
        if w_shifts is not None:
            return ref.add_conv2d_w4_ref(x, w, w_shifts, bias,
                                         requant_shift=requant_shift,
                                         x_preshift=x_preshift,
                                         w_preshift=w_preshift, act=act)
        if requant_shift is not None:
            return ref.add_conv2d_q8_ref(x, w, bias,
                                         requant_shift=requant_shift,
                                         x_preshift=x_preshift,
                                         w_preshift=w_preshift, act=act)
        if bias is not None or x_preshift or w_preshift:
            raise ValueError("add_conv2d: bias/preshifts without "
                             "requant_shift are only supported on the "
                             "quantized path")
        return ref.add_conv2d_ref(x, w, act=act)

    if method == "xla":
        _check_no_config(method, config)
        return _xla()
    if _is_degraded("add_conv2d"):
        return _xla()
    from repro.tune import sig_add_conv2d
    n, h, wd, cx = x.shape
    if config is None:
        config = _tuned(sig_add_conv2d, n, h, wd, cx, w.shape[-1], w.shape[0],
                        dtype=_w4_dtype(x, w_shifts))
    else:
        _check_explicit(sig_add_conv2d, n, h, wd, cx, w.shape[-1], w.shape[0],
                        config=config, dtype=_w4_dtype(x, w_shifts))
    return _pallas_guard("add_conv2d", lambda: _add_pallas(
        x, w, bias, requant_shift=requant_shift,
        x_preshift=x_preshift, w_preshift=w_preshift, act=act,
        interpret=use_interpret(), config=config, w_shifts=w_shifts), _xla)


def maxpool2d(x, *, window: int = 2, stride: Optional[int] = None,
              method: str = "pallas", config: Optional[dict] = None):
    """VALID max-pool, int8 or float. Pooling int8 codes is bit-exact with
    pooling the dequantized floats (max commutes with the positive pow2
    scale) — the graph executor's integer-only pool boundary."""
    _check_method(method)
    _count_dispatch("maxpool2d", method)

    def _xla():
        return ref.maxpool2d_ref(x, window=window, stride=stride)

    if method == "xla":
        _check_no_config(method, config)
        return _xla()
    if _is_degraded("maxpool2d"):
        return _xla()
    from repro.tune import sig_maxpool2d
    n, h, wd, c = x.shape
    if config is None:
        config = _tuned(sig_maxpool2d, n, h, wd, c, window, stride or window,
                        dtype=x.dtype)
    else:
        _check_explicit(sig_maxpool2d, n, h, wd, c, window, stride or window,
                        config=config, dtype=x.dtype)
    return _pallas_guard("maxpool2d", lambda: _pool_pallas(
        x, window=window, stride=stride,
        interpret=use_interpret(), config=config), _xla)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _causal_conv1d_diff(x, w, block_l, block_c):
    """Pallas forward + analytic jnp backward (pallas_call has no AD rule).

    bwd: dx is the anti-causal conv of g with the same taps (flip-conv-flip);
    dw[k,d] = sum_{b,l} g[b,l,d] * x_leftpad[b,l+k,d].
    """
    return _c1d_pallas(x, w, block_l=block_l, block_c=block_c,
                       interpret=use_interpret())


def _c1d_fwd(x, w, block_l, block_c):
    return _causal_conv1d_diff(x, w, block_l, block_c), (x, w)


def _c1d_bwd(block_l, block_c, res, g):
    x, w = res
    k = w.shape[0]
    gx = jnp.flip(_causal_conv1d_diff(jnp.flip(g, axis=1), w,
                                      block_l, block_c), axis=1)
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    l = x.shape[1]
    dw = jnp.stack([jnp.einsum("bld,bld->d", g.astype(jnp.float32),
                               xp[:, kk:kk + l, :].astype(jnp.float32))
                    for kk in range(k)], axis=0).astype(w.dtype)
    return gx, dw


_causal_conv1d_diff.defvjp(_c1d_fwd, _c1d_bwd)


def causal_conv1d(x, w, *, method: str = "auto",
                  config: Optional[dict] = None):
    """method='auto': Pallas kernel off-mesh (exercises the paper primitive);
    XLA path under SPMD — an opaque pallas_call would force its operands to
    be gathered/replicated by the partitioner. Pass ``config=`` only with an
    explicit method='pallas' request: the auto->xla resolution under a mesh
    must stay legal for schedule-pinned call sites, but a hard method='xla'
    with a config is the same conflicting-arguments error as everywhere
    else."""
    _check_method(method, ("auto", "pallas", "xla"))
    if method == "xla":
        _check_no_config(method, config)
    if method == "auto":
        from repro.parallel.sharding import current_mesh
        method = "xla" if current_mesh() is not None else "pallas"
        if method == "xla":     # auto degraded: opaque pallas_call vs SPMD
            _obs_metrics.counter("kernels.fallback.causal_conv1d.mesh").inc()
    _count_dispatch("causal_conv1d", method)
    if method == "xla":
        return ref.causal_conv1d_ref(x, w)
    if _is_degraded("causal_conv1d"):
        return ref.causal_conv1d_ref(x, w)
    from repro.tune import sig_causal_conv1d
    b, l, d = x.shape
    if config is None:
        config = _tuned(sig_causal_conv1d, b, l, d, w.shape[0], dtype=x.dtype)
    else:
        _check_explicit(sig_causal_conv1d, b, l, d, w.shape[0],
                        config=config, dtype=x.dtype)
    from repro.tune import default_config
    base = default_config("causal_conv1d")
    return _pallas_guard("causal_conv1d", lambda: _causal_conv1d_diff(
        x, w, int(config.get("block_l", base["block_l"])),
        int(config.get("block_c", base["block_c"]))),
        lambda: ref.causal_conv1d_ref(x, w))


def matmul(a, b, *, method: str = "pallas", requant_shift: Optional[int] = None,
           act: Optional[str] = None,
           bm: Optional[int] = None, bn: Optional[int] = None,
           bk: Optional[int] = None, config: Optional[dict] = None,
           w_shifts: Optional[jax.Array] = None):
    """Explicit bm/bn/bk win over ``config``, which wins over the tuner."""
    _check_method(method)
    _count_dispatch("matmul", method)

    def _xla():
        if w_shifts is not None:
            return ref.matmul_w4_ref(a, b, w_shifts,
                                     requant_shift=requant_shift, act=act)
        return ref.matmul_ref(a, b, requant_shift=requant_shift, act=act)

    if method == "xla":
        _check_no_config(method, config, bm, bn, bk)
        return _xla()
    if _is_degraded("matmul"):
        return _xla()
    from repro.tune import sig_matmul
    explicit = config is not None or any(v is not None for v in (bm, bn, bk))
    if config is None and None in (bm, bn, bk):
        config = _tuned(sig_matmul, a.shape[0], a.shape[1], b.shape[1],
                        dtype=_w4_dtype(a, w_shifts))
    config = dict(config or {})
    for name, val in (("bm", bm), ("bn", bn), ("bk", bk)):
        if val is not None:
            config[name] = val
    if explicit:
        _check_explicit(sig_matmul, a.shape[0], a.shape[1], b.shape[1],
                        config=config, dtype=_w4_dtype(a, w_shifts))
    return _pallas_guard("matmul", lambda: _mm_pallas(
        a, b, requant_shift=requant_shift, act=act,
        interpret=use_interpret(), config=config, w_shifts=w_shifts), _xla)
