"""Plan executor: one jit for the whole integer network.

:class:`CompiledPlan` takes a lowered :class:`~repro.graph.lower.Plan`,
resolves each node's execution method (pallas / xla) and tuned kernel
schedule ONCE (at first trace, via ``repro.tune``), and jits the entire
forward as a single function — the one-compiled-artifact-per-model regime
the ROADMAP's north star calls for. Inside the jit the activations stay
int8 from the input quantization to the global average pool: ReLU runs as
the conv kernels' accumulator-scale epilogue and pooling runs on int8 codes
(``kernels.ops.maxpool2d``), so there are zero float round-trips between
conv layers.

Three more entry points share the plan:

* :func:`float_forward` — the float inference interpreter over the IR
  (``models.convnet.cnn_forward``'s eval path).
* :func:`unfused_forward` — the OLD float-bounce regime reconstructed from
  the same plan (dequantize -> float ReLU/BN/pool -> requantize at the same
  annotated scales). Bit-exact with the fused path by construction (relu
  and max commute with the positive pow2 scale; requantization is monotone
  with ``rshift_round(0) == 0``) — pinned by tests/test_graph.py and used
  as the fused-vs-unfused baseline in benchmarks/layer_bench.py.
* :meth:`CompiledPlan.profile` — instrumented per-layer attribution:
  measured latency, analytic MACs and the paper-calibrated MCU
  latency/energy model per node ("Not All Ops Are Created Equal": cost is
  a per-layer, not per-network, quantity).

Observability (``repro.obs``): jit traces count into the process metrics
registry (``graph.compiles`` + a per-batch-bucket counter, and
``graph.fallback.xla`` when ``method="auto"`` degrades a node to the
oracle), and with ``REPRO_TRACE=1`` every ``__call__``/``forward_batch``
emits a span while ``profile`` emits one ``layer.<name>`` span per row —
the per-layer executor track in an exported Perfetto trace.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core.energy import MCUModel
from repro.core.qconv import _kernel_layer_ok, qconv_apply
from repro.core.quantize import QTensor, QTensorW4, quantize, requantize
from repro.kernels.common import apply_act
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from .ir import Graph
from .lower import Plan, PlanNode


def _node_dtype(node: PlanNode) -> str:
    """Tune-space dtype key for one qconv node: "w4a8" when its weights are
    nibble-packed (the W4-aware cost model prices halved weight traffic),
    else "int8"."""
    if any(isinstance(v, QTensorW4) for v in (node.qparams or {}).values()):
        return "w4a8"
    return "int8"


def _qbn_apply(qp: dict, x: QTensor, out_fb: int, act: Optional[str]) -> QTensor:
    """Integer per-channel BN affine: int8 act * int16-range multiplier +
    bias at accumulator scale, fused act, Algorithm-1 requantization. Pure
    int32 jnp — identical under both methods, so it never breaks
    pallas==xla."""
    acc = x.q.astype(jnp.int32) * qp["a"] + qp["b"]
    acc = apply_act(acc, act)
    return QTensor(requantize(acc, x.frac_bits + qp["a_frac_bits"], out_fb),
                   out_fb)


class CompiledPlan:
    """Callable integer-only forward for one lowered plan.

    ``method`` selects the kernel engine for every eligible node:
    ``"pallas"`` (fused TPU kernels; raises on layers outside the kernel
    envelope), ``"xla"`` (jnp integer oracles), or ``"auto"`` (pallas where
    expressible, oracle fallback elsewhere). Schedules come from the
    ``repro.tune`` cache/fallback, resolved once per compile and recorded in
    ``self.node_configs``.

    Every plan is statically verified at build (``repro.check``: dataflow
    legality + int32 accumulator/requant-shift safety from the actual
    weight codes) and every resolved schedule gets a hard VMEM feasibility
    verdict at first trace — ``validate=False`` opts out of both (e.g. for
    deliberately adversarial plans under test).
    """

    def __init__(self, plan: Plan, *, method: str = "auto", jit: bool = True,
                 validate: bool = True):
        if method not in ("pallas", "xla", "auto"):
            raise ValueError(f"unknown method {method!r}; expected "
                             "'pallas', 'xla' or 'auto'")
        if validate:
            from repro.check import validate_plan
            validate_plan(plan)
        self.plan = plan
        self.method = method
        self.validate = validate
        self.node_configs: Dict[str, dict] = {}
        self.traces = 0                  # python-side compile counter
        self.degraded = False            # degrade_to_xla happened (one-shot)
        self._jit = jit
        self._fn = jax.jit(self._forward) if jit else self._forward

    def degrade_to_xla(self):
        """ONE-SHOT graceful degradation: re-point every node at the jnp
        integer oracle (``method="xla"``) and re-jit the forward, dropping
        the compiled pallas artifact. The xla oracles are bit-exact with
        the pallas kernels (tests/test_kernels.py), so already-served
        results stay comparable — only throughput degrades. Called by the
        serving layer after repeated injected round failures; idempotent,
        logged once, counted as ``graph.degraded`` in the process metrics."""
        if self.degraded:
            return
        self.degraded = True
        self.method = "xla"
        self.node_configs = {}           # pallas schedules no longer apply
        self._fn = jax.jit(self._forward) if self._jit else self._forward
        obs_metrics.counter("graph.degraded").inc()
        import warnings
        warnings.warn(
            "CompiledPlan degraded to the xla reference path after repeated "
            "injected failure — serving continues bit-exact but slower",
            RuntimeWarning, stacklevel=2)

    # ------------------------------------------------------------- dispatch

    def _node_method(self, node: PlanNode) -> str:
        if (self.method == "auto" and node.op == "qconv"
                and not _kernel_layer_ok(node.spec)):
            return "xla"         # auto degrades to the oracle...
        # ...but an explicit "pallas" keeps the node on pallas so qconv_apply
        # raises for out-of-envelope layers instead of silently running xla
        return "pallas" if self.method in ("pallas", "auto") else "xla"

    def _resolve_configs(self, node: PlanNode, xq: QTensor) -> Optional[dict]:
        """Tuned-schedule lookup for one qconv node, keyed on the concrete
        traced shapes; runs once per compile (inside the single trace)."""
        if self._node_method(node) != "pallas":
            return None
        from repro import tune
        n, h, w, c = xq.q.shape
        spec = node.spec
        p = spec.primitive
        dt = _node_dtype(node)
        if p in ("standard", "grouped"):
            g = spec.groups if p == "grouped" else 1
            sigs = {"main": tune.sig_conv2d(n, h, w, c, spec.out_channels,
                                            spec.kernel_size, g)}
        elif p == "dws":
            sigs = {"dw": tune.sig_depthwise2d(n, h, w, c, spec.kernel_size),
                    "pw": tune.sig_conv2d(n, h, w, c, spec.out_channels,
                                          1, 1)}
        elif p == "shift":
            sigs = {"main": tune.sig_shift_conv2d(n, h, w, c,
                                                  spec.out_channels)}
        else:                            # add
            sigs = {"main": tune.sig_add_conv2d(n, h, w, c,
                                                spec.out_channels,
                                                spec.kernel_size)}
        cfg = {stage: tune.get_config(sig, dt) for stage, sig in sigs.items()}
        if self.validate:
            # hard feasibility gate on every resolved schedule: the tune
            # layer prunes its own candidates, but a stale/hand-edited cache
            # entry could still smuggle in an oversized block
            from repro.check import CheckError
            from repro.check.footprint import check_schedule
            bad = []
            for stage, sig in sigs.items():
                verdict = check_schedule(sig, cfg[stage], dt)
                if not verdict.ok:
                    bad.extend(f"{node.name}/{stage} "
                               f"[{sig.kernel}/{sig.key()}]: {e}"
                               for e in verdict.errors)
            if bad:
                raise CheckError(
                    f"infeasible kernel schedule for node {node.name!r} "
                    "(repro.check.check_schedule; pass validate=False to "
                    "bypass):", bad)
        self.node_configs[node.name] = cfg
        return cfg

    # -------------------------------------------------------------- forward

    def _run_node(self, node: PlanNode, h):
        from repro.kernels import ops as K
        if node.op == "qconv":
            m = self._node_method(node)
            if self.method == "auto" and m == "xla":
                # auto degraded to the oracle for this node (outside the
                # pallas kernel envelope) — count it so coverage regressions
                # of the kernel layer are visible in the metrics snapshot
                obs_metrics.counter("graph.fallback.xla").inc()
            return qconv_apply(node.qparams, h, node.spec, node.out_fb,
                               method=m, act=node.act,
                               configs=self._resolve_configs(node, h))
        if node.op == "qbn":
            return _qbn_apply(node.qparams, h, node.out_fb, node.act)
        if node.op == "maxpool":
            q = K.maxpool2d(h.q, window=node.attrs["window"],
                            stride=node.attrs["stride"],
                            method=self._node_method(node))
            return QTensor(q, h.frac_bits)
        if node.op == "gap":             # head boundary: int8 -> float
            return jnp.mean(h.dequantize(), axis=(1, 2))
        if node.op == "dense":
            return h @ node.qparams["w"]
        raise ValueError(node.op)

    def _forward(self, x):
        self.traces += 1                 # counts jit traces, not calls
        # compile-event counters (trace-time python side effects): one total
        # plus one per batch bucket, so recompile storms show up per shape
        obs_metrics.counter("graph.compiles").inc()
        obs_metrics.counter(f"graph.compiles.n{x.shape[0]}").inc()
        with obs_trace.span("plan.trace", n=x.shape[0], method=self.method):
            h = quantize(x, self.plan.in_fb)
            for node in self.plan.nodes:
                h = self._run_node(node, h)
            return h

    def __call__(self, x):
        with obs_trace.span("plan.forward", n=x.shape[0]):
            return self._fn(x)

    # ------------------------------------------------------ batched serving

    @staticmethod
    def batch_bucket(n: int) -> int:
        """Smallest power of two >= n: the batch sizes forward_batch
        actually compiles for, so arbitrary request counts cost at most
        O(log max_batch) traces."""
        b = 1
        while b < n:
            b *= 2
        return b

    def forward_batch(self, x):
        """Throughput entry point: one batched forward over a leading batch
        dim, zero-padded up to the pow2 batch bucket and cropped back, so
        the ONE plan jit is reused across ragged microbatches instead of
        retracing per batch size. The int8 trunk is bit-exact with the
        per-sample loop — every plan op is row-independent and the batched
        kernel grids accumulate each image's taps in the per-image order —
        while the float gap->dense head agrees only to ~1e-6 (and exactly
        by argmax): XLA picks batch-size-dependent float matmul kernels, so
        don't hash or exact-compare the logits across batch sizes."""
        n = x.shape[0]
        b = self.batch_bucket(n)
        with obs_trace.span("plan.forward_batch", n=n, bucket=b):
            if b != n:
                x = jnp.concatenate(
                    [x, jnp.zeros((b - n,) + x.shape[1:], x.dtype)])
            return self._fn(x)[:n]

    def throughput(self, x, *, reps: int = 5, warmup: int = 2) -> dict:
        """Measured images/s of the batched path at ``x``'s batch size
        (post-warmup, median-of-reps — the §Throughput headline number)."""
        from repro.tune.runner import time_config
        us = time_config(self.forward_batch, x, reps=reps, warmup=warmup)
        n = x.shape[0]
        return {"batch": n, "bucket": self.batch_bucket(n),
                "us_per_batch": us, "us_per_image": us / n,
                "images_per_s": 1e6 * n / us}

    # ------------------------------------------------- per-layer attribution

    def profile(self, x, *, f_mhz: float = 84.0, reps: int = 3,
                mode: str = "latency") -> List[dict]:
        """Instrumented execution: one row per plan node with measured
        latency (node jitted standalone), analytic MACs, and the
        paper-calibrated MCU latency/energy model (scalar vs SIMD) for the
        conv nodes — the paper's per-layer Table-2 reading.

        ``mode="throughput"`` reads the same rows as a traffic-serving
        profile: each row additionally carries the node's delivered
        ``images_per_s`` and amortized ``us_per_image`` at ``x``'s batch
        size (per-layer cost is a per-batch quantity under the tiled
        batched schedules, so profile at the batch you serve)."""
        if mode not in ("latency", "throughput"):
            raise ValueError(f"unknown profile mode {mode!r}; expected "
                             "'latency' or 'throughput'")
        from repro.tune.runner import time_config
        mcu = MCUModel()
        rows: List[dict] = []
        batch = x.shape[0]
        h = quantize(x, self.plan.in_fb)
        for node in self.plan.nodes:
            fn = jax.jit(lambda v, _n=node: self._run_node(_n, v))
            # per-layer span aligned with this row: one "layer.<name>" slice
            # per profile row, carrying the measured us as a span attribute
            with obs_trace.span(f"layer.{node.name}", cat="graph.profile",
                                op=node.op, batch=batch) as sp:
                us = time_config(fn, h, reps=reps, warmup=1)
                sp.set(us=us)
            row = dict(name=node.name, op=node.op, us=us, macs=0,
                       primitive=node.spec.primitive if node.spec else None)
            if node.op == "qconv":
                width = node.attrs["in_hw"][1]
                row["macs"] = node.spec.mac_count(width)
                row["mcu_lat_scalar_ms"] = 1e3 * mcu.latency_s(
                    node.spec, width, simd=False, f_mhz=f_mhz)
                row["mcu_lat_simd_ms"] = 1e3 * mcu.latency_s(
                    node.spec, width, simd=True, f_mhz=f_mhz)
                row["mcu_e_scalar_mj"] = mcu.energy_mj(
                    node.spec, width, simd=False, f_mhz=f_mhz)
                row["mcu_e_simd_mj"] = mcu.energy_mj(
                    node.spec, width, simd=True, f_mhz=f_mhz)
            if mode == "throughput":
                row["us_per_image"] = us / batch
                row["images_per_s"] = 1e6 * batch / us if us > 0 else 0.0
            h = fn(h)
            rows.append(row)
        return rows


# ---------------------------------------------------------------- references

def float_forward(graph: Graph, params: dict, x: jax.Array) -> jax.Array:
    """Float inference over the IR (BN inference buffers, no stat
    re-estimation) — the eval path of ``models.convnet.cnn_forward``; one
    walk of ``lower.interpret``, the same interpreter the calibration sweep
    runs."""
    from .lower import interpret
    return interpret(graph, params, x)["acts"][graph.output]


def unfused_forward(plan: Plan, x, *, method: str = "xla"):
    """The pre-graph float-bounce regime, reconstructed from the same plan:
    every layer dequantizes to float for ReLU/BN-act/pool and re-quantizes
    at the node's annotated scale before the next conv. Same integer conv
    arithmetic, same scales — bit-exact with :class:`CompiledPlan` (the
    fused epilogues commute with dequantization), but with the two float
    round-trips per block the fusion pass removes. Baseline side of
    ``benchmarks/layer_bench.py``'s fused-vs-unfused comparison."""
    h = quantize(x, plan.in_fb)
    for node in plan.nodes:
        if node.op == "qconv":
            yq = qconv_apply(node.qparams, h, node.spec, node.out_fb,
                             method=method, act=None)
            y = yq.dequantize()
            if node.act == "relu":
                y = jax.nn.relu(y)
            h = quantize(y, node.out_fb)
        elif node.op == "qbn":
            zq = _qbn_apply(node.qparams, h, node.out_fb, act=None)
            y = zq.dequantize()
            if node.act == "relu":
                y = jax.nn.relu(y)
            h = quantize(y, node.out_fb)
        elif node.op == "maxpool":
            from repro.kernels.ref import maxpool2d_ref
            y = maxpool2d_ref(h.dequantize(), window=node.attrs["window"],
                              stride=node.attrs["stride"])
            h = quantize(y, node.out_fb)
        elif node.op == "gap":
            h = jnp.mean(h.dequantize(), axis=(1, 2))
        elif node.op == "dense":
            h = h @ node.qparams["w"]
    return h
