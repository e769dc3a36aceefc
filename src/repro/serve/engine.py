"""Continuous-batching serve engine over a fixed (max_batch, max_len) budget.

Two schedulers share one ``Engine`` API; ``ServeConfig.scheduler`` picks:

* ``"continuous"`` (default) — a slot-based scheduler. Each admitted request
  is prefilled on its own (right-padded to a power-of-two length bucket so
  jit recompiles stay O(log max_len)), and its KV cache + position are
  surgically written into a free slot of the ONE live batched cache
  (``models/api.cache_write_slot``). Decode then advances every occupied
  slot one token per round with per-slot cache lengths (``cache["len"]`` is
  a (max_batch,) vector; each row writes/attends at its own position). A
  sequence retires the round it finishes — per-request EOS, per-request
  ``max_new_tokens``, or the ``max_len`` KV cap — and its freed slot is
  refilled from the queue *between decode rounds*, so the batch stays full
  under skewed output lengths instead of draining to the slowest member.
* ``"static"`` — the legacy drain strategy: pack up to ``max_batch``
  requests, left-pad prompts to a common length (unmasked, the historical
  approximation), prefill once, and decode the whole batch to completion
  before admitting more. Kept as the baseline that
  ``benchmarks/serve_bench.py`` measures continuous scheduling against.

Sampling is greedy argmax unless a ``Request`` carries a positive
``temperature``, which switches that request to softmax sampling with the
engine's seeded host rng. The decode call picks every row's greedy token
on the device (``models/api.decode_pick_fn``) and a round copies back
only those (B,) ids: ``Engine._pick`` gets, per row, a view that answers
``argmax()`` with the device's pick and copies its own logits row to the
host only for a request that samples (counted by
``serve.host_logit_rows``). Admission's first pick reads the prefill's
last-position logits on the host.

The jitted steps read ``api.serving_params(cfg, params)``: the matmul
weights cast to the compute dtype once, at construction, instead of in
every call (the same bits), beside the caller's tree, which is left as
it is. The gauge ``serve.cast_weight_bytes`` holds the copies' bytes (0
for a tree stored in the compute dtype).

The continuous loop runs over one KV arena (``repro.serve.arena``), picked
by ``ServeConfig.kv_layout``: a ``SlotArena`` (``"contiguous"``, the
default; every slot owns a (max_len,) KV row) or a ``PagedArena``
(``"paged"``; a shared :class:`BlockPool` of pages with hash-based prefix
reuse). The arena owns what the layout decides (admission's prefill and
cache write, page growth, the uploads before a round, releasing a slot);
the loop owns the policy: admission order, the FIFO holdback of requests
the pool cannot back yet, preemption of the youngest slot when growth
runs dry (greedy decode replays the identical stream), retirement and
retries. Greedy streams match across the layouts exactly for int8 KV;
float paged pools decode through the ``paged_attention`` kernel, whose
online softmax rounds differently from the contiguous attention.

``Engine.stats`` surfaces scheduler metrics: prefill/decode-round/token
counters, slot occupancy (occupied slot-rounds over offered slot-rounds),
TTFT/TPOT/queue-wait latency quantiles, decode throughput, and block-pool
gauges (``blocks_in_use`` / ``blocks_free`` / ``prefix_hit_rate``; zero
under the contiguous layout). The stats are backed by a private
``repro.obs.metrics.Registry`` per engine (same keys as the pre-registry
dict, plus the histogram quantiles).

Spans (``repro.obs.trace``; in the profiler's trace whenever a session is
active, on the device's clock): ``engine.drain`` around each
:meth:`Engine.run_until_drained`, and inside it, tiling each pass of the
continuous loop, ``engine.arena_init`` (the KV cache and ``BlockPool``
built per drain or rebuilt after a failure), ``engine.admit`` (one per
admission: prefix lookup, page allocation, ``engine.prefill``, the logits
copy and the first pick), ``engine.upload`` (page growth and the
``len`` / block-table uploads), ``engine.decode_round`` and
``engine.sample`` (the ids copy, the per-row picks, retirement;
``host_rows``, the rows whose logits came to the host, summed by
``serve.host_logit_rows``). The
counters ``serve.admit_time_s``, ``serve.upload_time_s``,
``serve.sample_time_s`` and ``serve.decode_time_s`` add up the
``perf_counter`` time of their spans. ``engine.decode_round`` carries
``pages``, the KV pages per layer the round's rows hold (each row's new
position included), which the paged attention reads (0 under the
contiguous layout); ``serve.kv_pages_read`` adds them up. A pattern stack
(LFM2) with experts also puts ``moe_experts_hit`` (experts given a token,
summed over its expert layers) and ``moe_assignments`` on
``engine.prefill`` and ``engine.decode_round``, read from the call's
``moe_counts`` after the engine waited on it, and adds them to the
counters ``serve.moe_experts_hit`` and ``serve.moe_assignments``. With
``REPRO_TRACE=1`` the same spans and per-request lifecycle lanes
(queue_wait -> prefill -> generate, each request on its own lane) also go
to the Chrome export — write it with ``repro.obs.trace.export(path)`` and
open it in Perfetto.

Timing discipline: decode-round timers ``jax.block_until_ready`` the round
outputs before stopping, so ``decode_tok_s`` measures real device time and
not JAX async-dispatch enqueue time; request timestamps are monotonic
``time.perf_counter()`` values (intervals can't go negative under clock
adjustment) with one wall-clock field (``submit_wall_t``) kept for trace
export.

Failure model (EXPERIMENTS.md §Resilience): the engine degrades instead of
dying. Every request reaches exactly one terminal ``status``:

* ``ok``      — retired normally (EOS / max_new_tokens / KV cap).
* ``timeout`` — its ``deadline_s`` (per-request, falling back to
  ``ServeConfig.deadline_s``) elapsed; cancelled at a round boundary with
  partial ``out_tokens``, KV slot and pool pages reclaimed.
* ``error``   — a prefill or decode failure survived
  ``ServeConfig.max_retries`` bounded retries (exponential
  ``retry_backoff_s``); only the poisoned request(s) retire, survivors
  keep decoding bit-identically (batch composition never changes a
  greedy stream). An unrecoverable *decode-round* failure retires the
  whole active set and rebuilds the KV arena (donated buffers may be
  dead), then drains the queue against the fresh arena.
* ``shed``    — rejected at ``submit`` because the queue held
  ``max_queue`` requests (``shed_policy="reject"`` raises
  :class:`QueueFullError` instead of marking).

Fault seams (``repro.faults``): ``engine.prefill`` fires per admission
attempt, ``engine.decode_round`` per round attempt, ``blockpool.alloc``
inside :meth:`BlockPool.alloc`. A ``corrupt`` fault poisons that round's
host logits (a corrupt round copies every row's logits and picks from
them); the affected uids are recorded in ``Engine.poisoned_uids``
(silent corruption is contained, not detected). With no plan active every
seam is a single global read.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import queue
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.faults import inject as faults
from repro.models import api
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.arena import Arena, BlockPool, make_arena


class QueueFullError(RuntimeError):
    """Raised by :meth:`Engine.submit` under ``shed_policy="reject"`` when
    the queue already holds ``max_queue`` requests."""


@dataclasses.dataclass
class Request:
    """One generation request plus the engine-filled result/metric fields."""
    uid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None    # overrides ServeConfig.eos_id when set
    temperature: Optional[float] = None  # > 0 samples; None/<= 0: argmax
    deadline_s: Optional[float] = None   # overrides ServeConfig.deadline_s
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = "pending"         # terminal: ok | timeout | error | shed
    error: Optional[str] = None     # the absorbed exception, status="error"
    # engine-filled metrics — monotonic time.perf_counter() stamps, so the
    # derived intervals (ttft, queue wait, tpot) can never go negative under
    # wall-clock adjustment; submit_wall_t is the one wall-clock field kept
    # so trace export can recover absolute times
    submit_t: float = 0.0           # perf_counter at Engine.submit
    submit_wall_t: float = 0.0      # wall clock at Engine.submit
    admit_t: float = 0.0            # perf_counter at slot admission
    first_token_t: float = 0.0      # perf_counter when the prefill token landed
    finish_t: float = 0.0
    admit_round: int = -1           # global decode-round counter at admission
    finish_round: int = -1          # round the request retired on

    @property
    def ttft_s(self) -> float:
        return max(self.first_token_t - self.submit_t, 0.0)

    @property
    def queue_wait_s(self) -> float:
        return max(self.admit_t - self.submit_t, 0.0)


@dataclasses.dataclass
class ServeConfig:
    """Engine knobs.

    max_batch:  number of decode slots — the batch dim of the KV budget.
    max_len:    per-slot KV capacity. prompt length + generated tokens are
                capped here; a sequence that fills its slot is retired even
                if it has not reached ``max_new_tokens`` / EOS.
    eos_id:     stop-token id. ``-1`` is the "never" sentinel — no token id
                can equal it, so only ``max_new_tokens`` or the ``max_len``
                cap retire a sequence. ``Request.eos_id`` overrides per
                request (including overriding a real id back to -1).
    scheduler:  "continuous" (slot refill between decode rounds) or
                "static" (legacy drain batches).
    prefill_bucket: floor of the power-of-two right-padding buckets used by
                continuous prefill for attention families. ssm/hybrid
                recurrences are position-exact, so those families always
                prefill at the exact prompt length (one compile per
                distinct length).
    seed:       host rng seed for a request's temperature sampling.
    precision:  "float" (default) serves as-is. "int8" PTQ-quantizes every
                layer's FFN weights at engine init (power-of-two scales,
                paper Eq. 4) and runs those matmuls int8 x int8 -> int32
                through the Pallas ``matmul_q8`` kernel with its fused
                Algorithm-1 shift-requantized epilogue; "int8-xla" is the
                same arithmetic on the jnp integer oracle (bit-exact with
                "int8" — the direct / no-SIMD baseline). "w4a8" additionally
                nibble-packs the FFN weights (4-bit codes + per-group shift
                scales, ``quantize_w4``) and the matmul unpacks them
                in-register — half the weight bytes per decode step at the
                same int8 activation path. Attention-family dense-MLP
                configs only (no moe / ssm / hybrid / encdec).
    kv_cache:   "float" (default) keeps the resident KV cache in the model
                compute dtype. "int8" stores K/V as int8 codes with
                per-(position, head) f32 scales — ~halved KV bytes;
                quantize-on-write, dequantize-on-read, per-token scales so
                slot refill/retire never re-scales a neighbour. Continuous
                scheduler + attention-family dense caches only (the static
                path decodes straight off the float prefill cache).
    kv_layout:  "contiguous" (default) gives every slot a (max_len,) KV
                row. "paged" backs K/V with a BlockPool of kv_num_blocks
                fixed-size pages instead — block tables grow on demand and
                a shared prompt prefix is stored (and, for float KV,
                prefilled) once. Greedy streams match the contiguous
                layout exactly for int8 KV; float pools decode through the
                ``paged_attention`` kernel, whose online softmax rounds
                differently. Continuous scheduler + attention-family dense
                caches only; composes with kv_cache="int8" (int8 pool
                pages + scale pages).
    kv_block_size: positions per page under kv_layout="paged". Must divide
                max_len (a slot's block table then spans exactly max_len
                positions).
                Smaller pages waste less tail memory but hash/grow more
                often; prefix sharing is full-page-granular.
    kv_num_blocks: pool size under kv_layout="paged", including the
                reserved garbage page 0. None (default) sizes the pool to
                the contiguous capacity equivalent, max_batch *
                (max_len / kv_block_size) + 1 — same KV budget, so paged
                admission/growth can never be the bottleneck. Must leave
                at least max_len / kv_block_size usable pages (one request
                growing to max_len must always be able to finish).
    prefix_cache: hash full prompt pages for reuse (paged layout only).
                True by default; disable to measure pure paging.
    deadline_s: per-request wall budget, measured from ``submit``. None
                (default) disables. Checked at round boundaries and before
                admission — an expired request retires with
                ``status="timeout"`` (partial ``out_tokens`` kept, KV slot
                and pool pages reclaimed). ``Request.deadline_s`` overrides
                per request.
    max_queue:  queue-depth cap enforced at :meth:`Engine.submit`. None
                (default) is unbounded; when set it must be
                ``>= max_batch`` (repro.check.config) so one full batch can
                always queue.
    shed_policy: what a full queue does to the incoming request:
                "reject" (default) raises :class:`QueueFullError`;
                "drop" marks it terminal ``status="shed"`` without
                enqueueing (the caller still holds the object).
    max_retries: bounded retries for an injected/transient prefill or
                decode failure before the poisoned request(s) retire with
                ``status="error"``. 0 disables retrying.
    retry_backoff_s: base of the exponential retry backoff sleep
                (``base * 2**(attempt-1)``). 0 (default) retries
                immediately — the right setting for deterministic tests.
    """
    max_batch: int = 4
    max_len: int = 256
    eos_id: int = -1                # -1: never
    scheduler: str = "continuous"
    prefill_bucket: int = 16
    seed: int = 0
    precision: str = "float"
    kv_cache: str = "float"
    kv_layout: str = "contiguous"
    kv_block_size: int = 16
    kv_num_blocks: Optional[int] = None
    prefix_cache: bool = True
    deadline_s: Optional[float] = None
    max_queue: Optional[int] = None
    shed_policy: str = "reject"
    max_retries: int = 2
    retry_backoff_s: float = 0.0


@jax.jit
def _logit_row(logits, i):
    """Row ``i`` of a decode round's (B, 1, V) logits, last position."""
    return logits[i, -1]


class _DeviceRow:
    """One row of a decode round's logits, left on the device, as
    :meth:`Engine._pick` reads it: ``argmax()`` is the token the decode
    call picked for the row, ``shape`` the row's, ``(V,)``, and
    ``np.asarray`` copies the row (only the row) to the host and marks it
    ``fetched``. A greedy pick therefore moves no logits."""
    __slots__ = ("_logits", "_i", "_id", "shape", "fetched")

    def __init__(self, logits, i: int, token: int):
        self._logits, self._i, self._id = logits, i, token
        self.shape = logits.shape[2:]
        self.fetched = False

    def argmax(self) -> int:
        return self._id

    def __array__(self, dtype=None, copy=None):
        self.fetched = True
        return np.asarray(_logit_row(self._logits, np.int32(self._i)), dtype)


class Engine:
    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig):
        # one place decides what is served (repro.check.config): the
        # combinations of model, scheduler, cache and precision the engine
        # does not serve raise NotImplementedError; unknown values, bad
        # sizes and the like raise ValueError (scripts/check_plan.py runs
        # the strict set)
        from repro.check.config import check_serve_config, unsupported
        no = unsupported(scfg, cfg)
        if no:
            raise NotImplementedError("; ".join(no))
        bad = check_serve_config(scfg, cfg, strict=False)
        if bad:
            raise ValueError("invalid ServeConfig:\n"
                             + "\n".join(f"  - {m}" for m in bad))
        if scfg.precision != "float":
            # PTQ the FFN stack once; the quantized tree rides along in
            # params["layers"] so the layer scan slices it like any weight.
            # w4a8: same tree, but nibble-packed QTensorW4 leaves
            from repro.models.blocks import quantize_mlp_params
            layers = dict(params["layers"])
            layers["qmlp"] = quantize_mlp_params(
                layers["mlp"], bits=4 if scfg.precision == "w4a8" else 8)
            params = dict(params, layers=layers)
        # after the PTQ, which reads the float32 FFN weights: the steps
        # get the matmul weights cast once, here, not in every call
        kept = {id(x) for x in jax.tree_util.tree_leaves(params)}
        params = api.serving_params(cfg, params)
        self._cast_weight_bytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(params)
            if id(x) not in kept)
        self.cfg = cfg
        self.scfg = scfg
        self.params = params
        self.prefill = jax.jit(
            api.prefill_fn(cfg, scfg.max_len, precision=scfg.precision))
        # donate the live cache so slot writes / decode rounds update it in
        # place instead of copying the whole KV budget (CPU backends don't
        # implement donation and would warn on every compile, so skip there)
        cpu = jax.default_backend() == "cpu"
        # the decode step returns each row's greedy pick beside the logits
        # and the cache (one jit); a round copies back the (B,) ids only
        self.decode = jax.jit(
            api.decode_pick_fn(cfg, precision=scfg.precision),
            donate_argnums=() if cpu else (2,))
        if cfg.family != "encdec":
            self._write_slot = jax.jit(
                functools.partial(api.cache_write_slot, cfg),
                donate_argnums=() if cpu else (0,))
        # a pattern stack's expert layers count the assignments each expert
        # took (cache["moe_counts"]), read after each prefill and round
        self._moe_counts = bool(cfg.layer_types) and cfg.n_moe_layers > 0
        if scfg.kv_layout == "paged":
            # page-granular cache surgery: scatter prefilled K/V into pool
            # pages, gather shared prefix pages back out, and the
            # suffix-only prefill that makes float-KV prefix hits cheap
            self._write_pages = jax.jit(
                functools.partial(api.paged_write_prompt, cfg),
                static_argnames=("src", "skip_blocks"),
                donate_argnums=() if cpu else (0,))
            self._write_kv = jax.jit(api.paged_write_kv,
                                     donate_argnums=() if cpu else (0,))
            # per-slot state beside the pages (short-convolution state)
            self._write_state = jax.jit(
                functools.partial(api.write_slot_state, cfg),
                donate_argnums=() if cpu else (0,)) \
                if api.slot_state_axes(cfg) else None
            self._gather_prefix = jax.jit(api.paged_gather_prefix)
            if scfg.prefix_cache and scfg.kv_cache == "float":
                self.prefill_suffix = jax.jit(api.prefill_suffix_fn(
                    cfg, precision=scfg.precision))
        self.queue: "queue.Queue[Request]" = queue.Queue()
        self._rng = np.random.default_rng(scfg.seed)
        # private registry: per-engine stats isolation; handles stay valid
        # across reset_stats (Registry.reset zeroes in place)
        self.metrics = obs_metrics.Registry()
        self._m = {
            "prefills": self.metrics.counter("serve.prefills"),
            "decode_steps": self.metrics.counter("serve.decode_steps"),
            # KV pages per layer the paged decode rounds read (the pages
            # each active row holds, its new position included)
            "kv_pages_read": self.metrics.counter("serve.kv_pages_read"),
            "tokens_out": self.metrics.counter("serve.tokens_out"),
            "requests_done": self.metrics.counter("serve.requests_done"),
            "occupied": self.metrics.counter("serve.occupied_slot_rounds"),
            "decode_time": self.metrics.counter("serve.decode_time_s"),
            # host phases of a continuous round, each the perf_counter time
            # of its span (engine.admit / engine.upload / engine.sample)
            "admit_time": self.metrics.counter("serve.admit_time_s"),
            "upload_time": self.metrics.counter("serve.upload_time_s"),
            "sample_time": self.metrics.counter("serve.sample_time_s"),
            # decode-round rows whose logits were copied to the host (a
            # temperature row, or every row of a corrupt-directive round)
            "host_logit_rows": self.metrics.counter("serve.host_logit_rows"),
            # expert layers (pattern stacks): per call, experts given at
            # least one token, and (token, expert) assignments, summed
            "moe_experts_hit": self.metrics.counter("serve.moe_experts_hit"),
            "moe_assignments": self.metrics.counter("serve.moe_assignments"),
            "ttft": self.metrics.histogram("serve.ttft_s"),
            "tpot": self.metrics.histogram("serve.tpot_s"),
            "queue_wait": self.metrics.histogram("serve.queue_wait_s"),
            # block-pool gauges: live under kv_layout="paged", zero under
            # contiguous (registered unconditionally for stats key parity)
            "blocks_in_use": self.metrics.gauge("serve.blocks_in_use"),
            "blocks_free": self.metrics.gauge("serve.blocks_free"),
            "prefix_hit_rate": self.metrics.gauge("serve.prefix_hit_rate"),
            # bytes of the compute-dtype weight copies made at construction
            # (api.serving_params); 0 when the tree is stored in it
            "cast_weight_bytes": self.metrics.gauge(
                "serve.cast_weight_bytes"),
            # resilience counters (EXPERIMENTS.md §Resilience): terminal
            # statuses other than "ok", retry attempts, arena rebuilds
            "timeouts": self.metrics.counter("serve.timeouts"),
            "errors": self.metrics.counter("serve.errors"),
            "shed": self.metrics.counter("serve.shed"),
            "retries": self.metrics.counter("serve.retries"),
            "arena_rebuilds": self.metrics.counter("serve.arena_rebuilds"),
        }
        self.reset_stats()

    # ------------------------------------------------------------- metrics --

    def reset_stats(self):
        """Zero the counters (e.g. after a compile-warmup drain)."""
        self.metrics.reset()
        self._m["cast_weight_bytes"].set(self._cast_weight_bytes)
        self._round = 0
        # uids whose logits an injected "corrupt" fault poisoned — silent
        # corruption is contained (recorded), not detected; the chaos
        # harness excludes these from the bit-identity comparison
        self.poisoned_uids: set = set()
        # the live BlockPool (paged runs only) — exposed so the chaos
        # harness can audit conservation after a drain
        self.pool: Optional[BlockPool] = None

    @property
    def stats(self) -> dict:
        """Counters + derived scheduler metrics (computed on access from the
        engine's registry). Key-compatible with the pre-registry dict
        (prefills/decode_steps/tokens_out/requests_done/occupancy/
        ttft_avg_s/decode_tok_s) plus the histogram quantiles."""
        m = self._m
        rounds = int(m["decode_steps"].value)
        c = dict(prefills=int(m["prefills"].value),
                 decode_steps=rounds,
                 tokens_out=int(m["tokens_out"].value),
                 requests_done=int(m["requests_done"].value))
        c["occupancy"] = (m["occupied"].value
                          / (rounds * self.scfg.max_batch)) if rounds else 0.0
        c["ttft_avg_s"] = m["ttft"].mean
        decode_time = m["decode_time"].value
        c["decode_tok_s"] = (c["tokens_out"] / decode_time
                             if decode_time > 0 else 0.0)
        c["ttft_p50_s"] = m["ttft"].percentile(50)
        c["ttft_p95_s"] = m["ttft"].percentile(95)
        c["ttft_p99_s"] = m["ttft"].percentile(99)
        c["tpot_avg_s"] = m["tpot"].mean
        c["queue_wait_avg_s"] = m["queue_wait"].mean
        c["queue_wait_p99_s"] = m["queue_wait"].percentile(99)
        c["blocks_in_use"] = int(m["blocks_in_use"].value)
        c["blocks_free"] = int(m["blocks_free"].value)
        c["prefix_hit_rate"] = float(m["prefix_hit_rate"].value)
        c["timeouts"] = int(m["timeouts"].value)
        c["errors"] = int(m["errors"].value)
        c["shed"] = int(m["shed"].value)
        c["retries"] = int(m["retries"].value)
        c["arena_rebuilds"] = int(m["arena_rebuilds"].value)
        return c

    def _count_experts(self, cache: dict, sp):
        """Add the expert counts of the call that produced ``cache`` (its
        ``moe_counts``, (L_moe, E) assignments per expert) to the counters
        and to span ``sp``. A small copy to the host after a call the
        engine already waited on."""
        c = np.asarray(cache["moe_counts"])
        hit, n = int(np.count_nonzero(c)), int(c.sum())
        self._m["moe_experts_hit"].inc(hit)
        self._m["moe_assignments"].inc(n)
        sp.set(moe_experts_hit=hit, moe_assignments=n)

    def _observe_retired(self, req: Request):
        """Latency histograms + the request's trace-lane replay (the spans
        are emitted at retirement from recorded perf_counter stamps, so
        overlapping requests land on separate, properly nested lanes)."""
        self._m["queue_wait"].observe(req.queue_wait_s)
        n_out = len(req.out_tokens)
        if n_out > 1 and req.finish_t > req.first_token_t:
            self._m["tpot"].observe(
                (req.finish_t - req.first_token_t) / (n_out - 1))
        tr = obs_trace.TRACER
        if tr.enabled:
            lane = obs_trace.next_lane()
            tr.begin("request", ts=req.submit_t, tid=lane, uid=req.uid,
                     prompt_len=int(len(req.prompt)), new_tokens=n_out,
                     submit_wall_t=req.submit_wall_t)
            tr.complete("queue_wait", req.submit_t, req.admit_t, tid=lane)
            tr.complete("prefill", req.admit_t, req.first_token_t, tid=lane)
            tr.complete("generate", req.first_token_t, req.finish_t, tid=lane,
                        tokens=n_out)
            tr.end("request", ts=req.finish_t, tid=lane)

    # ----------------------------------------------------------- frontend --

    def _validate_prompt_len(self, req: Request):
        """THE prompt-length check — submit and admit share it, so both
        reject with one message (they used to diverge)."""
        if len(req.prompt) > self.scfg.max_len:
            raise ValueError(
                f"request {req.uid}: prompt length {len(req.prompt)} exceeds "
                f"max_len={self.scfg.max_len}")

    def submit(self, req: Request):
        # reject oversized prompts here, not mid-drain: raising during
        # run_until_drained would discard finished requests and strand the
        # rest of the queue
        self._validate_prompt_len(req)
        req.submit_t = time.perf_counter()
        req.submit_wall_t = time.time()
        # load shedding: overload rejects at the door instead of growing the
        # queue unboundedly (the engine is single-threaded, so qsize is exact)
        mq = self.scfg.max_queue
        if mq is not None and self.queue.qsize() >= mq:
            self._m["shed"].inc()
            if self.scfg.shed_policy == "reject":
                raise QueueFullError(
                    f"request {req.uid}: queue holds max_queue={mq} "
                    f"requests (shed_policy='reject')")
            req.done = True             # "drop": terminal without enqueue
            req.status = "shed"
            req.finish_t = time.perf_counter()
            return
        self.queue.put(req)

    # ----------------------------------------------------------- deadlines --

    def _deadline_of(self, req: Request) -> Optional[float]:
        return (req.deadline_s if req.deadline_s is not None
                else self.scfg.deadline_s)

    def _expired(self, req: Request, now: Optional[float] = None) -> bool:
        d = self._deadline_of(req)
        if d is None:
            return False
        if now is None:
            now = time.perf_counter()
        return (now - req.submit_t) > d

    def _next_request(self) -> Optional[Request]:
        try:
            return self.queue.get_nowait()
        except queue.Empty:
            return None

    def _take_batch(self) -> List[Request]:
        # get_nowait, not .empty(): .empty() is only a racy hint once a
        # producer thread (or future async frontend) feeds the queue
        out: List[Request] = []
        while len(out) < self.scfg.max_batch:
            req = self._next_request()
            if req is None:
                break
            out.append(req)
        return out

    def run_until_drained(self) -> List[Request]:
        with obs_trace.span("engine.drain", scheduler=self.scfg.scheduler):
            if self.scfg.scheduler == "static":
                return self._run_static()
            return self._run_continuous()

    # ----------------------------------------------------------- sampling --

    def _pick(self, logits_row, req: Request) -> int:
        """THE place a served token is chosen. ``logits_row`` is a (V,)
        numpy row (admission, the static path, a corrupt round) or a
        decode round's :class:`_DeviceRow`, whose ``argmax()`` is the
        device's pick and whose logits come to the host only here, for a
        request that samples."""
        temp = req.temperature
        if temp is None or temp <= 0.0:
            return int(logits_row.argmax())
        z = np.asarray(logits_row, np.float64) / temp
        z -= z.max()
        p = np.exp(z)
        return int(self._rng.choice(p.size, p=p / p.sum()))

    def _effective_eos(self, req: Request) -> int:
        return self.scfg.eos_id if req.eos_id is None else req.eos_id

    # ------------------------------------------- shared by both schedulers --

    def _bucket_len(self, plen: int) -> int:
        # oversized prompts were already rejected by _validate_prompt_len
        # (at submit, and again at admit for directly enqueued requests)
        if self.cfg.family in ("ssm", "hybrid"):
            return plen                 # recurrent state is position-exact
        b = max(self.scfg.prefill_bucket, 1)
        while b < plen:
            b *= 2
        return min(b, self.scfg.max_len)

    def _padded(self, toks: np.ndarray) -> np.ndarray:
        """``toks`` right-padded to its length bucket, as a (1, bucket)
        row: positions 0..len-1 hold the tokens."""
        out = np.zeros((1, self._bucket_len(len(toks))), np.int32)
        out[0, :len(toks)] = toks
        return out

    def _prefill(self, tokens: np.ndarray, prompt_lens=None, store=None,
                 **attrs):
        """THE prompt prefill, in one ``engine.prefill`` span (``attrs``):
        ``tokens`` (b, S) are right-padded rows with their
        ``prompt_lens``, or the static batch's left-padded rows. ``store``
        gets the fresh cache (an arena's KV write), dispatched before the
        logits come to the host. Returns the host logits and the fresh
        cache."""
        batch = {"tokens": jnp.asarray(tokens)}
        if prompt_lens is not None:
            batch["prompt_lens"] = jnp.asarray(prompt_lens, jnp.int32)
        with obs_trace.span("engine.prefill", **attrs) as sp:
            logits, fresh = self.prefill(self.params, batch)
            self._m["prefills"].inc()
            if store is not None:
                store(fresh)
            logits = np.asarray(logits)
            if self._moe_counts:
                self._count_experts(fresh, sp)
        return logits, fresh

    def _retry_call(self, site: str, fn, *args, **kwargs):
        """``fn(*args, **kwargs)`` behind fault site ``site``, retried up
        to ``max_retries`` times with exponential backoff. The site fires
        before ``fn`` makes any device call or pool change, so only an
        ``InjectedFault`` retries: a real exception may have consumed
        donated buffers. Returns ``(result, fired, err)``: on success err
        is None and fired the corrupt directive, if one fired; otherwise
        result is None and err the absorbed exception."""
        err: Optional[BaseException] = None
        for attempt in range(self.scfg.max_retries + 1):
            if attempt:
                self._m["retries"].inc()
                if self.scfg.retry_backoff_s > 0:
                    time.sleep(self.scfg.retry_backoff_s
                               * (2 ** (attempt - 1)))
            try:
                fired = faults.check(site)
                return fn(*args, **kwargs), fired, None
            except faults.InjectedFault as e:
                err = e
            except Exception as e:
                return None, None, e
        return None, None, err

    def _retire(self, req: Request, status: str,
                err: Optional[str] = None) -> Request:
        """THE terminal bookkeeping of every request the engine finishes
        (``ok``, ``timeout``, ``error``), admitted or not, continuous or
        static: stamps, counters, latency histograms and trace lanes. The
        caller releases the request's slot."""
        now = time.perf_counter()
        req.done = True
        req.status = status
        if err is not None:
            req.error = err
        req.admit_t = req.admit_t or now           # never admitted
        req.first_token_t = req.first_token_t or now   # no first token
        req.finish_t = now
        req.finish_round = self._round
        self._m["requests_done"].inc()
        if status != "ok":
            self._m["timeouts" if status == "timeout" else "errors"].inc()
        self._observe_retired(req)
        return req

    def _finished(self, req: Request, n: int) -> bool:
        """``req``, holding ``n`` KV positions, is done: its last token is
        its EOS, it has ``max_new_tokens``, or it reached the max_len cap."""
        return (req.out_tokens[-1] == self._effective_eos(req)
                or len(req.out_tokens) >= req.max_new_tokens
                or n >= self.scfg.max_len)

    def _decode_round(self, cur: np.ndarray, cache: dict, active: int,
                      pages: int = 0):
        """One decode call over the batch, in the ``engine.decode_round``
        span: ``(ids, logits, cache)``. The timer stops after
        ``block_until_ready`` of every output, so ``decode_tok_s`` is
        device time and not async-dispatch enqueue time. ``pages`` is what
        a paged round reads (``serve.kv_pages_read``)."""
        t0 = time.perf_counter()
        with obs_trace.span("engine.decode_round", round=self._round,
                            active=active) as sp:
            sp.set(pages=pages)
            out = self.decode(self.params, jnp.asarray(cur), cache)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            if self._moe_counts:
                self._count_experts(out[2], sp)
        self._m["decode_time"].inc(dt)
        self._m["kv_pages_read"].inc(pages)
        return out

    # --------------------------------------------------------- continuous --

    def _new_arena(self, rebuild: bool = False) -> Arena:
        """THE arena constructor: at each drain's start, and in place of
        the old one after a decode round failed for good (the decode jit
        donates the cache, so its buffers may be dead; a paged pool starts
        empty too, since its digests name pages of the old cache)."""
        attrs = {}
        if rebuild:
            self._m["arena_rebuilds"].inc()
            attrs["rebuild"] = True
        with obs_trace.span("engine.arena_init", layout=self.scfg.kv_layout,
                            **attrs):
            arena = make_arena(self)
        self.pool = arena.pool
        return arena

    def _run_continuous(self) -> List[Request]:
        arena = self._new_arena()
        # requests the paged pool could not back yet, or preempted; they
        # go before the queue, so admission stays FIFO (empty under the
        # contiguous layout, which never runs short)
        holdback: "collections.deque[Request]" = collections.deque()
        finished: List[Request] = []
        stalls = 0                      # passes with nothing to decode
        while True:
            # 1. refill free slots between rounds
            self._refill(arena, holdback, finished)
            if not arena.order:
                if not holdback:
                    break               # the queue is drained
                # can't-admit stall: nothing active to retire and the
                # holdback head still does not fit. Give retirements
                # max_retries+1 passes to change that, then retire the head
                # as "error" instead of deadlocking
                stalls += 1
                if stalls > self.scfg.max_retries:
                    finished.append(self._retire(
                        holdback.popleft(), "error",
                        "paged KV pool cannot admit this request even "
                        "with every page reclaimable — kv_num_blocks "
                        "is below its worst-case page need"))
                    stalls = 0
                continue
            stalls = 0
            # 2. grow and upload: when the pool cannot back a row's next
            # page, preempt the youngest admission. It restarts from its
            # prompt through the holdback: greedy decode replays the same
            # stream, and its published prompt pages usually survive as
            # evictable, so the re-prefill hits. The discarded tokens stay
            # in tokens_out (they were real decode work)
            for _ in arena.prepare():
                req = arena.release(arena.order[-1])
                req.out_tokens, req.done = [], False
                holdback.appendleft(req)
            active = [i for i, r in enumerate(arena.reqs) if r is not None]
            if not active:
                continue                # preemption emptied the batch
            # 3. decode, with retry
            got, fired, err = self._retry_call(
                "engine.decode_round", self._decode_round, arena.cur,
                arena.cache, len(active), arena.pages(active))
            if err is not None:
                # the batch shares one donated cache, so no request can be
                # singled out: retire the active set as "error" and go on
                # draining against a fresh arena
                for i in active:
                    finished.append(self._retire(arena.reqs[i], "error",
                                                 repr(err)))
                arena = self._new_arena(rebuild=True)
                continue
            ids, logits, arena.cache = got
            # 4. sample and retire
            with obs_trace.span("engine.sample", active=len(active)) as sp:
                if fired is None:
                    # each row's pick was made on the device; a row's
                    # logits reach the host only if its request samples
                    ids = np.asarray(ids)
                    rows = {i: _DeviceRow(logits, i, int(ids[i]))
                            for i in active}
                else:
                    # corrupt directive: poison this round's host logits;
                    # every request sampled from them is contained, not
                    # fixed
                    rows = fired.apply(np.asarray(logits))[:, -1]
                    self.poisoned_uids.update(arena.reqs[i].uid
                                              for i in active)
                self._round += 1
                self._m["decode_steps"].inc()
                self._m["occupied"].inc(len(active))
                now = time.perf_counter()
                reqs, lens, cur = arena.reqs, arena.lens, arena.cur
                for i in active:
                    lens[i] += 1        # this round wrote K/V at lens[i]
                    req = reqs[i]
                    t = self._pick(rows[i], req)
                    req.out_tokens.append(t)
                    self._m["tokens_out"].inc()
                    cur[i, 0] = t
                    if self._finished(req, lens[i]):
                        finished.append(self._retire(req, "ok"))
                        arena.release(i)
                    elif self._expired(req, now):   # round-boundary cancel
                        finished.append(self._retire(req, "timeout"))
                        arena.release(i)
                host_rows = len(active) if fired is not None else \
                    sum(rows[i].fetched for i in active)
                sp.set(host_rows=host_rows)
                self._m["host_logit_rows"].inc(host_rows)
            self._m["sample_time"].inc(sp.seconds)
            # 5. upload len
            with obs_trace.span("engine.upload") as sp:
                arena.upload_len()
            self._m["upload_time"].inc(sp.seconds)
        return finished

    def _refill(self, arena: Arena, holdback, finished: List[Request]):
        """Admit into every free slot, the holdback before the queue. A
        slot whose request finished at admission (max_new_tokens=1, an
        instant EOS, a failed admission) refills at once; a request
        already past its deadline retires unadmitted (its prefill would be
        wasted). A request the pool cannot back goes back to the head of
        the holdback and ends the refill until retirements release
        pages."""
        for i in range(self.scfg.max_batch):
            while arena.reqs[i] is None:
                req = holdback.popleft() if holdback else self._next_request()
                if req is None:
                    break
                if self._expired(req):
                    finished.append(self._retire(req, "timeout"))
                elif self._admit(arena, i, req, finished) == "full":
                    holdback.appendleft(req)
                    return

    def _admit(self, arena: Arena, i: int, req: Request,
               finished: List[Request]) -> str:
        """Admit ``req`` into free slot ``i`` in one ``engine.admit`` span:
        the arena's prefill (retried), the logits copy, the first pick,
        and retirement if that already finishes the request. Returns "ok",
        "full" (the paged pool cannot back the prompt) or "failed" (the
        admission outlived max_retries: retired as "error")."""
        with obs_trace.span("engine.admit", uid=req.uid,
                            plen=len(req.prompt)) as sp:
            self._validate_prompt_len(req)   # directly enqueued requests
            logits, fired, err = self._retry_call("engine.prefill",
                                                  arena.admit, i, req, sp)
            if err is not None:
                res = "failed"
                finished.append(self._retire(req, "error", repr(err)))
            elif logits is None:
                res = "full"
            else:
                res = "ok"
                if fired is not None:   # corrupt directive: poison the
                    logits = fired.apply(logits)   # sampled logits only
                    self.poisoned_uids.add(req.uid)
                t = self._pick(logits[0, -1], req)
                req.first_token_t = time.perf_counter()
                req.admit_round = self._round
                req.out_tokens.append(t)
                self._m["tokens_out"].inc()
                self._m["ttft"].observe(req.ttft_s)
                arena.cur[i, 0] = t
                if self._finished(req, arena.lens[i]):
                    finished.append(self._retire(req, "ok"))
                    arena.release(i)
            sp.set(result=res)
        self._m["admit_time"].inc(sp.seconds)
        return res

    # ------------------------------------------------------------- static --

    def _run_static(self) -> List[Request]:
        finished: List[Request] = []
        while True:
            batch = self._take_batch()
            if not batch:
                break
            finished.extend(self._run_batch(batch))
        return finished

    def _run_batch(self, reqs: List[Request]) -> List[Request]:
        b = len(reqs)
        plen = max(len(r.prompt) for r in reqs)
        toks = np.zeros((b, plen), np.int32)
        for i, r in enumerate(reqs):
            toks[i, plen - len(r.prompt):] = r.prompt      # left-pad
        now = time.perf_counter()
        for r in reqs:
            r.admit_t = now
        got, fired, err = self._retry_call("engine.prefill", self._prefill,
                                           toks, batch=b, plen=plen)
        if err is not None:
            # the batch shares one prefill: retire it whole; the next
            # _take_batch keeps draining the queue
            return [self._retire(r, "error", repr(err)) for r in reqs]
        lg, cache = got
        if fired is not None:
            lg = fired.apply(lg)
            self.poisoned_uids.update(r.uid for r in reqs)
        cur = np.zeros((b, 1), np.int32)
        now = time.perf_counter()
        with obs_trace.span("engine.sample", active=b) as sp:
            for i, r in enumerate(reqs):
                t = self._pick(lg[i, -1], r)
                r.first_token_t = now
                self._m["ttft"].observe(r.ttft_s)
                r.out_tokens.append(t)
                self._m["tokens_out"].inc()
                cur[i, 0] = t
                if t == self._effective_eos(r) or r.max_new_tokens <= 1:
                    r.done = True
        self._m["sample_time"].inc(sp.seconds)
        steps = max(r.max_new_tokens for r in reqs) - 1
        for _ in range(max(steps, 0)):
            now = time.perf_counter()
            for r in reqs:
                if not r.done and self._expired(r, now):
                    self._retire(r, "timeout")  # round-boundary cancel
            if all(r.done for r in reqs):
                break
            got, fired, err = self._retry_call(
                "engine.decode_round", self._decode_round, cur, cache,
                sum(not r.done for r in reqs))
            if err is not None:
                break
            _, lg, cache = got
            lg = np.asarray(lg)
            if fired is not None:
                lg = fired.apply(lg)
                self.poisoned_uids.update(
                    r.uid for r in reqs if not r.done)
            self._round += 1
            self._m["decode_steps"].inc()
            with obs_trace.span("engine.sample",
                                active=sum(not r.done for r in reqs)) as sp:
                for i, r in enumerate(reqs):
                    if r.done:
                        continue
                    self._m["occupied"].inc()
                    t = self._pick(lg[i, -1], r)
                    r.out_tokens.append(t)
                    self._m["tokens_out"].inc()
                    cur[i, 0] = t
                    if (t == self._effective_eos(r)
                            or len(r.out_tokens) >= r.max_new_tokens):
                        r.done = True
            self._m["sample_time"].inc(sp.seconds)
        for r in reqs:
            if r.status != "pending":
                continue                # timed out mid-batch
            if err is not None and not r.done:
                # one shared (donated) cache: no per-request attribution
                self._retire(r, "error", repr(err))
            else:
                self._retire(r, "ok")
        return reqs
