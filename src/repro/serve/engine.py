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

Sampling is greedy argmax by default; a positive temperature (per
``ServeConfig`` with ``greedy=False``, or per-``Request`` override) switches
that request to softmax sampling with the engine's seeded host rng. The
decode call picks every row's greedy token on the device
(``models/api.decode_pick_fn``) and a round copies back only those (B,)
ids: ``Engine._pick`` gets, per row, a view that answers ``argmax()`` with
the device's pick and copies its own logits row to the host only for a
request that samples with a temperature (counted by
``serve.host_logit_rows``). Admission's first pick reads the prefill's
last-position logits on the host.

KV backing is picked by ``ServeConfig.kv_layout``:

* ``"contiguous"`` (default) — every slot owns a (max_len,) KV row of the
  one live batched cache; memory is ``max_batch x max_len`` regardless of
  the actual sequence lengths.
* ``"paged"`` — K/V live in a shared :class:`BlockPool` of fixed-size
  pages (``kv_block_size`` positions each); each slot holds a block table
  that grows one page at a time as the sequence crosses a page boundary,
  so resident KV scales with *actual* tokens. Prompt pages are
  content-addressed (chained sha1 over full prompt blocks): requests that
  share a prompt prefix map their leading table entries to the same
  refcounted pages, paying the prefix's prefill FLOPs and KV bytes once —
  on a float-KV hit only the suffix runs through the model
  (``models/api.prefill_suffix_fn``); int8-KV hits share storage only
  (dequantized codes are not the float prefix, so the prompt is recomputed
  and the shared-page writes skipped). Pages of retired requests linger in
  an LRU "evictable" set until memory pressure reclaims them, so serial
  repeats of a prefix still hit. When the pool runs dry the engine parks
  new admissions in a FIFO holdback (backpressure) and, for mid-decode
  growth, preempts the youngest slot (greedy decode makes the replayed
  stream identical). Greedy token streams are BIT-IDENTICAL to the
  contiguous layout for float and int8 KV alike: pages gather back into
  exactly the contiguous cache view (``kv_block_size`` divides
  ``max_len``), masked tail positions carry exact-zero attention weight,
  and a prefix page's K/V are bitwise independent of the bucket the donor
  prefilled under (tests/test_paged.py locks both properties). A pattern
  stack's short convolutions keep a per-slot state beside the pages
  (``models/api.slot_state_axes``): written from the prefill at
  admission, carried by decode, cleared at retirement and preemption (a
  preempted request's re-prefill rebuilds it); its prefix cache is
  refused, since a hit would need that state at the page boundary.

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
``perf_counter`` time of their spans. Under the paged layout,
``engine.decode_round`` carries ``pages``, the KV pages per layer the
round's rows hold (each row's new position included), which the paged
attention reads; ``serve.kv_pages_read`` adds them up. A pattern stack
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
import hashlib
import queue
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.faults import inject as faults
from repro.models import api
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


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
    temperature: Optional[float] = None  # overrides the engine default
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


class BlockPool:
    """Host-side page allocator + hash-based prefix cache for the paged KV
    layout (``ServeConfig.kv_layout="paged"``).

    Page 0 is RESERVED as the garbage page: never allocated, so a retired
    slot's zeroed block-table row scatters its masked (never-read) decode
    writes there without touching a live page.

    Prompt pages are content-addressed: ``prefix_keys`` chains a sha1 over
    each FULL prompt block (every digest covers all tokens up to and
    including its block, so equal digest == equal token prefix), and
    ``publish`` registers digest -> page after the page's K/V are written.
    A page whose live refcount drops to zero is NOT freed — it parks in an
    LRU *evictable* set with its digest mapping intact, so a later request
    with the same prefix still hits (serial-traffic TTFT wins); ``alloc``
    reclaims evictable pages oldest-first only once the free list runs
    dry. Retention is safe because published pages are never written again
    (decode writes land strictly past the last full prompt block) and
    content-addressing guarantees a hit returns K/V computed from exactly
    the hitting request's token prefix.

    Single-threaded by design — the engine drives it between device calls.

    Integrity: the pool validates its own transitions instead of silently
    corrupting the free list — ``free`` / ``release`` raise ``ValueError``
    on a double-free, an unknown page id, a page with live references, or
    a parked (evictable) page; ``acquire`` revalidates that a refcount-0
    page it is un-parking was not evicted in the meantime. :meth:`audit`
    returns every violated structural invariant (conservation
    ``free + live-in-use + parked == usable``, positive refcounts,
    digest bijection) as a list — the chaos harness and the property
    sweep in ``tests/test_faults.py`` call it after every op/drain.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 prefix_cache: bool = True):
        if num_blocks < 2:
            raise ValueError("BlockPool needs >= 2 pages (page 0 is the "
                             "reserved garbage page)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.prefix_cache = prefix_cache
        # pop() -> lowest id first; freed pages return LIFO (deterministic)
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._allocated: set = set()          # handed out, not yet freed
        self._ref: Dict[int, int] = {}        # page id -> live refcount
        self._digest: Dict[str, int] = {}     # digest -> page id
        self._page_digest: Dict[int, str] = {}
        self._evictable: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()         # refcount-0 hashed pages, LRU
        self.lookups = 0                      # block-granular hit telemetry
        self.hits = 0

    # ------------------------------------------------------------ capacity --

    @property
    def usable(self) -> int:
        return self.num_blocks - 1

    @property
    def free_pages(self) -> int:
        """Allocatable pages: truly free plus evictable-on-demand."""
        return len(self._free) + len(self._evictable)

    @property
    def in_use(self) -> int:
        return self.usable - self.free_pages

    # -------------------------------------------------------- prefix cache --

    def prefix_keys(self, prompt: np.ndarray) -> List[str]:
        """Chained sha1 digest per full prompt block, excluding the block
        holding the last prompt token — at least one position is always
        recomputed so admission has last-token logits to sample from."""
        if not self.prefix_cache:
            return []
        bs = self.block_size
        toks = np.ascontiguousarray(np.asarray(prompt, np.int32))
        h = hashlib.sha1()
        keys = []
        for j in range((len(toks) - 1) // bs):
            h.update(toks[j * bs:(j + 1) * bs].tobytes())
            keys.append(h.hexdigest())
        return keys

    def lookup(self, keys: List[str]) -> List[int]:
        """Page ids for the longest registered leading run of ``keys``.
        Read-only: ``acquire`` the result before any ``alloc`` so eviction
        cannot reclaim a page the caller is about to reference."""
        ids = []
        for k in keys:
            bid = self._digest.get(k)
            if bid is None:
                break
            ids.append(bid)
        self.lookups += len(keys)
        self.hits += len(ids)
        return ids

    def acquire(self, ids: List[int]) -> None:
        """Take a live reference on hashed pages (un-parks evictable ones).

        A refcount-0 page being un-parked is revalidated: it must still be
        parked with its digest mapping intact — if ``alloc``'s eviction scan
        reclaimed it since the lookup, referencing it would alias a page
        now owned by another request, so that is a ``ValueError``."""
        for bid in ids:
            if bid in self._ref:
                self._ref[bid] += 1
                continue
            if bid not in self._evictable or bid not in self._page_digest:
                raise ValueError(
                    f"acquire: page {bid} has no live references and is not "
                    f"parked evictable — it was evicted (or never published); "
                    f"re-run lookup before acquiring")
            self._evictable.pop(bid)
            self._ref[bid] = 1

    def release(self, ids: List[int]) -> None:
        """Drop a live reference; pages reaching zero park as evictable.
        Releasing a page with no live reference (double-release, or an id
        that was never acquired/published) is a ``ValueError``."""
        for bid in ids:
            if self._ref.get(bid, 0) < 1:
                raise ValueError(
                    f"release: page {bid} has no live reference "
                    f"(double-release or unknown page id)")
            self._ref[bid] -= 1
            if self._ref[bid] == 0:
                del self._ref[bid]
                self._evictable[bid] = None

    def publish(self, keys: List[str], ids: List[int]) -> None:
        """Register freshly written full prompt blocks (digest -> page) and
        take the writing request's live reference. The engine is
        single-threaded, so a digest that missed at lookup is still absent
        here — no collision handling needed."""
        for k, bid in zip(keys, ids):
            self._digest[k] = bid
            self._page_digest[bid] = k
            self._ref[bid] = self._ref.get(bid, 0) + 1

    # --------------------------------------------------------- allocation --

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` pages, or None when the pool cannot supply them (the
        engine then applies admission backpressure / preemption). Evicts
        LRU refcount-0 hashed pages only when the free list runs dry.
        Fault seam ``blockpool.alloc`` fires before any state changes, so
        an injected raise never half-allocates."""
        faults.check("blockpool.alloc")
        if n > self.free_pages:
            return None
        out = []
        for _ in range(n):
            if self._free:
                out.append(self._free.pop())
            else:
                bid, _ = self._evictable.popitem(last=False)
                del self._digest[self._page_digest.pop(bid)]
                out.append(bid)
        self._allocated.update(out)
        return out

    def free(self, ids: List[int], hashed: int = 0) -> None:
        """Return a retired request's pages: the leading ``hashed`` ids
        (published/hit prompt pages) drop a reference and park when it
        reaches zero; the rest go straight back to the free list.

        The unhashed tail is validated before any state changes: every id
        must be a currently allocated page with no live references, not
        parked evictable, and not published — a double-free or unknown id
        raises ``ValueError`` instead of silently corrupting the free
        list (the old behavior, which later handed one page to two
        requests)."""
        tail = ids[hashed:]
        for bid in tail:
            if bid not in self._allocated:
                raise ValueError(
                    f"free: page {bid} is not allocated "
                    f"(double-free or unknown page id)")
            if self._ref.get(bid, 0) > 0:
                raise ValueError(
                    f"free: page {bid} has {self._ref[bid]} live "
                    f"reference(s) — release them (hashed=) instead")
            if bid in self._evictable or bid in self._page_digest:
                raise ValueError(
                    f"free: page {bid} is published/parked — published "
                    f"prompt pages retire via the hashed= prefix")
        self.release(ids[:hashed])
        self._allocated.difference_update(tail)
        self._free.extend(tail)

    # ------------------------------------------------------------ auditing --

    def audit(self, expect_drained: bool = False) -> List[str]:
        """Every violated structural invariant, as human-readable strings
        (empty == healthy). With ``expect_drained=True`` additionally
        requires quiescence: no live references and no allocated page
        outside the evictable set (i.e. nothing leaked after a drain)."""
        bad: List[str] = []
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            bad.append("duplicate ids on the free list")
        if 0 in free_set or 0 in self._allocated:
            bad.append("reserved garbage page 0 entered circulation")
        both = free_set & self._allocated
        if both:
            bad.append(f"pages simultaneously free and allocated: "
                       f"{sorted(both)}")
        if len(self._free) + len(self._allocated) != self.usable:
            bad.append(
                f"conservation broken: free({len(self._free)}) + "
                f"allocated({len(self._allocated)}) != usable({self.usable})")
        for bid, r in self._ref.items():
            if r < 1:
                bad.append(f"page {bid} has non-positive refcount {r}")
            if bid not in self._allocated:
                bad.append(f"referenced page {bid} is not allocated")
            if bid in self._evictable:
                bad.append(f"page {bid} parked evictable with live refs")
        for bid in self._evictable:
            if bid not in self._allocated:
                bad.append(f"evictable page {bid} is not allocated")
            if bid not in self._page_digest:
                bad.append(f"evictable page {bid} has no digest mapping")
        for d, bid in self._digest.items():
            if self._page_digest.get(bid) != d:
                bad.append(f"digest bijection broken at digest {d[:12]}…")
        for bid, d in self._page_digest.items():
            if self._digest.get(d) != bid:
                bad.append(f"digest bijection broken at page {bid}")
        if expect_drained:
            if self._ref:
                bad.append(f"live references after drain: "
                           f"{dict(sorted(self._ref.items()))}")
            leaked = self._allocated - set(self._evictable) - set(self._ref)
            if leaked:
                bad.append(f"leaked pages (allocated, unreferenced, not "
                           f"parked): {sorted(leaked)}")
        return bad


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
    greedy:     True -> argmax decoding (ignores ``temperature``).
    temperature: softmax temperature used when ``greedy=False`` (or when a
                request carries its own ``temperature`` override). <= 0
                degrades to argmax.
    scheduler:  "continuous" (slot refill between decode rounds) or
                "static" (legacy drain batches).
    prefill_bucket: floor of the power-of-two right-padding buckets used by
                continuous prefill for attention families. ssm/hybrid
                recurrences are position-exact, so those families always
                prefill at the exact prompt length (one compile per
                distinct length).
    attn_impl:  prefill attention implementation ("flash" | "full" | ...).
    seed:       host rng seed for temperature sampling.
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
                fixed-size pages instead — block tables grow on demand, a
                shared prompt prefix is stored (and, for float KV,
                prefilled) once, and greedy streams stay bit-identical to
                the contiguous layout. Continuous scheduler +
                attention-family dense caches only; composes with
                kv_cache="int8" (int8 pool pages + scale pages).
    kv_block_size: positions per page under kv_layout="paged". Must divide
                max_len (the gathered block-table view then spans exactly
                max_len positions — the bit-exactness precondition).
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
    greedy: bool = True
    temperature: float = 0.0
    scheduler: str = "continuous"
    prefill_bucket: int = 16
    attn_impl: str = "flash"
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
        self.cfg = cfg
        self.scfg = scfg
        self.params = params
        self.prefill = jax.jit(
            api.prefill_fn(cfg, scfg.max_len, attn_impl=scfg.attn_impl,
                           precision=scfg.precision))
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
                    cfg, attn_impl=scfg.attn_impl,
                    precision=scfg.precision))
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

    def _update_pool_gauges(self, pool: BlockPool):
        self._m["blocks_in_use"].set(pool.in_use)
        self._m["blocks_free"].set(pool.free_pages)
        self._m["prefix_hit_rate"].set(
            pool.hits / pool.lookups if pool.lookups else 0.0)

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
        if temp is None:
            temp = 0.0 if self.scfg.greedy else self.scfg.temperature
        if temp <= 0.0:
            return int(logits_row.argmax())
        z = np.asarray(logits_row, np.float64) / temp
        z -= z.max()
        p = np.exp(z)
        return int(self._rng.choice(p.size, p=p / p.sum()))

    def _effective_eos(self, req: Request) -> int:
        return self.scfg.eos_id if req.eos_id is None else req.eos_id

    # --------------------------------------------------------- continuous --

    def _bucket_len(self, plen: int) -> int:
        # oversized prompts were already rejected by _validate_prompt_len
        # (at submit, and again at admit for directly enqueued requests)
        if self.cfg.family in ("ssm", "hybrid"):
            return plen                 # recurrent state is position-exact
        b = max(self.scfg.prefill_bucket, 1)
        while b < plen:
            b *= 2
        return min(b, self.scfg.max_len)

    def _run_continuous(self) -> List[Request]:
        B = self.scfg.max_batch
        paged = self.scfg.kv_layout == "paged"
        bs = self.scfg.kv_block_size
        with obs_trace.span("engine.arena_init",
                            layout=self.scfg.kv_layout):
            if paged:
                from repro.check.config import paged_num_blocks
                nblocks = paged_num_blocks(self.scfg)
                cache = api.init_paged_cache(self.cfg, B, nblocks, bs,
                                             self.scfg.max_len,
                                             kv=self.scfg.kv_cache)
                pool = BlockPool(nblocks, bs,
                                 prefix_cache=self.scfg.prefix_cache)
                self.pool = pool        # audited by repro.faults.chaos
                table = np.zeros((B, self.scfg.max_len // bs), np.int32)
                slot_ids: List[List[int]] = [[] for _ in range(B)]
                slot_hashed = [0] * B   # leading refcounted pages per slot
                holdback: "collections.deque[Request]" = collections.deque()
                self._update_pool_gauges(pool)
            else:
                cache = api.init_slot_cache(self.cfg, B, self.scfg.max_len,
                                            kv=self.scfg.kv_cache)
        slots: List[Optional[Request]] = [None] * B
        admit_seq = [0] * B             # admission order, for victim choice
        seq = 0
        lens = [0] * B                  # host mirror of cache["len"]
        cur = np.zeros((B, 1), np.int32)
        finished: List[Request] = []

        def next_request() -> Optional[Request]:
            # holdback (pool-backpressured / preempted) drains before the
            # queue so paged admission stays FIFO
            if paged and holdback:
                return holdback.popleft()
            return self._next_request()

        def admit_paged(i: int, req: Request, plen: int, sp):
            """Returns last-position logits, or None when the pool cannot
            supply the prompt's pages (admission backpressure). Exception-
            safe: any failure after pages were referenced/allocated rolls
            the pool back before re-raising, so a retried (or retired)
            admission never leaks pages. ``sp`` is the engine.admit span,
            which gets the page counts."""
            nonlocal cache
            nb = -(-plen // bs)         # pages covering positions [0, plen)
            keys = pool.prefix_keys(req.prompt)
            hit_ids = pool.lookup(keys)
            n_hit = len(hit_ids)
            sp.set(hit_blocks=n_hit, new_blocks=nb - n_hit)
            # reference the hit pages BEFORE alloc so its eviction scan
            # cannot reclaim them out from under this admission
            pool.acquire(hit_ids)
            try:
                fresh = pool.alloc(nb - n_hit)
            except Exception:
                pool.release(hit_ids)   # injected blockpool.alloc fault
                raise
            if fresh is None:
                pool.release(hit_ids)
                return None
            req.admit_t = time.perf_counter()
            ids = hit_ids + fresh
            try:
                return _admit_paged_prefill(i, req, plen, keys, hit_ids,
                                            fresh, ids, nb)
            except Exception:
                pool.release(hit_ids)
                pool.free(fresh)        # unpublished: straight back
                self._update_pool_gauges(pool)
                raise

        def _admit_paged_prefill(i, req, plen, keys, hit_ids, fresh, ids,
                                 nb):
            nonlocal cache
            n_hit = len(hit_ids)
            fids = np.asarray(fresh, np.int32)
            if n_hit and "k_scale" not in cache:
                # float-KV prefix hit: the shared pages already hold the
                # prefix K/V — gather them and run ONLY the suffix (the
                # near-zero-TTFT path)
                pfx = n_hit * bs
                s_sfx = plen - pfx
                sbucket = self._bucket_len(s_sfx)
                toks = np.zeros((sbucket,), np.int32)
                toks[:s_sfx] = req.prompt[pfx:]
                with obs_trace.span("engine.prefill", uid=req.uid, slot=i,
                                    plen=plen, bucket=sbucket,
                                    prefix_hit=pfx):
                    pk, pv = self._gather_prefix(
                        cache, np.asarray(hit_ids, np.int32))
                    logits, ks, vs = self.prefill_suffix(self.params, {
                        "tokens": jnp.asarray(toks[None, :]),
                        "prefix_k": pk, "prefix_v": pv,
                        "suffix_lens": jnp.asarray([s_sfx], jnp.int32)})
                    self._m["prefills"].inc()
                    cache = self._write_kv(cache, ks, vs, fids)
                    logits = np.asarray(logits)
            else:
                # prefix miss — or an int8-KV hit, which shares STORAGE
                # only: dequantized codes are not the float prefix the
                # suffix math needs, so recompute the whole prompt and
                # just skip writing the shared pages
                bucket = self._bucket_len(plen)
                toks = np.zeros((bucket,), np.int32)
                toks[:plen] = req.prompt
                with obs_trace.span("engine.prefill", uid=req.uid, slot=i,
                                    plen=plen, bucket=bucket,
                                    prefix_hit=n_hit * bs) as psp:
                    logits, fresh_cache = self.prefill(self.params, {
                        "tokens": jnp.asarray(toks[None, :]),
                        "prompt_lens": jnp.asarray([plen], jnp.int32)})
                    self._m["prefills"].inc()
                    cache = self._write_pages(cache, fresh_cache, fids,
                                              skip_blocks=n_hit)
                    if self._write_state is not None:
                        cache = self._write_state(cache, fresh_cache,
                                                  jnp.int32(i))
                    logits = np.asarray(logits)
                    if self._moe_counts:
                        self._count_experts(fresh_cache, psp)
            # publish-at-admission: the fresh full prompt pages now hold
            # their final K/V (decode writes land strictly past them)
            pool.publish(keys[n_hit:], ids[n_hit:len(keys)])
            slot_ids[i] = ids
            slot_hashed[i] = len(keys)
            table[i, :nb] = ids
            table[i, nb:] = 0
            self._update_pool_gauges(pool)
            return logits

        def try_admit(i: int, req: Request) -> str:
            """Admit ``req`` into free slot ``i`` inside one engine.admit
            span (an admission that finishes its request at once retires
            it there too). Returns "ok", "full" (paged pool backpressure —
            park in the holdback), or "failed" (the admission survived
            max_retries and the request was retired with status="error")."""
            with obs_trace.span("engine.admit", uid=req.uid,
                                plen=len(req.prompt)) as sp:
                res = admit_attempts(i, req, sp)
                sp.set(result=res)
                if res == "ok":
                    maybe_retire(i)
            self._m["admit_time"].inc(sp.seconds)
            return res

        def admit_attempts(i: int, req: Request, sp) -> str:
            """try_admit's attempts. The ``engine.prefill`` fault seam
            fires once per attempt, BEFORE any device call or pool
            mutation, so an injected raise is always retry-safe."""
            nonlocal cache, seq
            self._validate_prompt_len(req)   # directly enqueued requests
            plen = len(req.prompt)
            last_err: Optional[BaseException] = None
            for attempt in range(self.scfg.max_retries + 1):
                if attempt:
                    self._m["retries"].inc()
                    if self.scfg.retry_backoff_s > 0:
                        time.sleep(self.scfg.retry_backoff_s
                                   * (2 ** (attempt - 1)))
                try:
                    fired = faults.check("engine.prefill")
                    if paged:
                        logits = admit_paged(i, req, plen, sp)
                        if logits is None:
                            return "full"
                    else:
                        bucket = self._bucket_len(plen)
                        req.admit_t = time.perf_counter()
                        toks = np.zeros((bucket,), np.int32)
                        toks[:plen] = req.prompt  # right-pad: 0..plen-1
                        with obs_trace.span("engine.prefill", uid=req.uid,
                                            slot=i, plen=plen,
                                            bucket=bucket):
                            logits, fresh = self.prefill(self.params, {
                                "tokens": jnp.asarray(toks[None, :]),
                                "prompt_lens": jnp.asarray([plen],
                                                           jnp.int32)})
                            self._m["prefills"].inc()
                            cache = self._write_slot(cache, fresh,
                                                     jnp.int32(i))
                            logits = np.asarray(logits)
                except faults.InjectedFault as e:
                    last_err = e        # fired pre-dispatch: retry is safe
                    continue
                except Exception as e:
                    last_err = e        # real failure: state may be gone
                    break               # (donated buffers) — do not retry
                if fired is not None:   # corrupt directive: poison the
                    logits = fired.apply(logits)   # sampled logits only
                    self.poisoned_uids.add(req.uid)
                t = self._pick(logits[0, -1], req)
                req.first_token_t = time.perf_counter()
                req.admit_round = self._round
                req.out_tokens.append(t)
                self._m["tokens_out"].inc()
                self._m["ttft"].observe(req.ttft_s)
                cur[i, 0] = t
                slots[i] = req
                lens[i] = plen
                admit_seq[i] = seq
                seq += 1
                return "ok"
            retire_unadmitted(req, "error", repr(last_err))
            return "failed"

        def retire_unadmitted(req: Request, status: str,
                              err: Optional[str] = None):
            """Terminal bookkeeping for a request that never held a slot
            (queue/holdback deadline expiry, failed admission)."""
            now = time.perf_counter()
            req.done = True
            req.status = status
            req.error = err
            if req.admit_t == 0.0:
                req.admit_t = now
            if req.first_token_t == 0.0:
                req.first_token_t = now
            req.finish_t = now
            req.finish_round = self._round
            finished.append(req)
            self._m["requests_done"].inc()
            self._m["timeouts" if status == "timeout" else "errors"].inc()
            self._observe_retired(req)

        def retire_slot(i: int, status: str = "ok",
                        err: Optional[str] = None):
            """Retire slot ``i``'s request with terminal ``status`` and
            reclaim its KV slot + pool pages — THE slot-release path, so
            ok/timeout/error retirement can never diverge on cleanup."""
            nonlocal cache
            req = slots[i]
            req.done = True
            req.status = status
            if err is not None:
                req.error = err
            req.finish_t = time.perf_counter()
            req.finish_round = self._round
            finished.append(req)
            self._m["requests_done"].inc()
            if status == "timeout":
                self._m["timeouts"].inc()
            elif status == "error":
                self._m["errors"].inc()
            self._observe_retired(req)
            slots[i] = None
            lens[i] = 0
            if paged:
                pool.free(slot_ids[i], hashed=slot_hashed[i])
                slot_ids[i] = []
                slot_hashed[i] = 0
                table[i, :] = 0
                self._update_pool_gauges(pool)
            cache = api.cache_free_slot(cache, i)

        def maybe_retire(i: int):
            req = slots[i]
            full = lens[i] >= self.scfg.max_len
            if (req.out_tokens[-1] == self._effective_eos(req)
                    or len(req.out_tokens) >= req.max_new_tokens or full):
                retire_slot(i, "ok")

        def preempt(victim: int):
            """Evict the youngest slot mid-decode to free its pages. Its
            request restarts from the prompt via the holdback — greedy
            decode replays the identical stream (and its published prompt
            pages usually survive as evictable, so the re-prefill hits)."""
            nonlocal cache
            req = slots[victim]
            pool.free(slot_ids[victim], hashed=slot_hashed[victim])
            # the discarded tokens stay in tokens_out (they were real decode
            # work); the replay after re-admission counts its own
            req.out_tokens = []
            req.done = False
            holdback.appendleft(req)
            slots[victim] = None
            lens[victim] = 0
            slot_ids[victim] = []
            slot_hashed[victim] = 0
            table[victim, :] = 0
            cache = api.cache_free_slot(cache, victim)
            self._update_pool_gauges(pool)

        def pool_alloc(n: int) -> Optional[List[int]]:
            """``pool.alloc`` with the injected-fault seam absorbed: an
            InjectedFault degrades to a transient shortage (None), which
            the callers already handle via backpressure/preemption — so a
            blockpool.alloc fault can never escape mid-decode."""
            try:
                return pool.alloc(n)
            except faults.InjectedFault:
                self._m["retries"].inc()
                return None

        def grow_tables() -> int:
            """Allocate the next page for every slot whose write position
            reached a page boundary; under pool pressure preempt youngest-
            admitted slots (oldest-first processing guarantees progress —
            a lone grower can always reclaim evictable pages). Returns the
            number of pages allocated."""
            order = sorted((i for i in range(B) if slots[i] is not None),
                           key=lambda i: admit_seq[i])
            grown = 0
            for i in order:
                if slots[i] is None:        # preempted by an older grower
                    continue
                pos = lens[i]
                if pos >= self.scfg.max_len or pos % bs \
                        or pos // bs < len(slot_ids[i]):
                    continue
                got = pool_alloc(1)
                while got is None:
                    victim = max((v for v in range(B)
                                  if slots[v] is not None),
                                 key=lambda v: admit_seq[v])
                    preempt(victim)
                    if victim == i:
                        break
                    got = pool_alloc(1)
                if slots[i] is None or got is None:
                    continue
                slot_ids[i].append(got[0])
                table[i, pos // bs] = got[0]
                grown += 1
            self._update_pool_gauges(pool)
            return grown

        def rebuild_arena():
            """Fresh KV arena after an unrecoverable decode failure: the
            decode jit donates the cache on accelerator backends, so the
            old buffers must be assumed dead. The paged pool restarts
            empty too — its prefix digests would otherwise resolve to
            pages of the reset arena."""
            nonlocal cache, pool
            self._m["arena_rebuilds"].inc()
            with obs_trace.span("engine.arena_init",
                                layout=self.scfg.kv_layout, rebuild=True):
                if paged:
                    cache = api.init_paged_cache(self.cfg, B, nblocks, bs,
                                                 self.scfg.max_len,
                                                 kv=self.scfg.kv_cache)
                    pool = BlockPool(nblocks, bs,
                                     prefix_cache=self.scfg.prefix_cache)
                    self.pool = pool
                    table[:] = 0
                    for i in range(B):
                        slot_ids[i] = []
                        slot_hashed[i] = 0
                    self._update_pool_gauges(pool)
                else:
                    cache = api.init_slot_cache(self.cfg, B,
                                                self.scfg.max_len,
                                                kv=self.scfg.kv_cache)

        stalls = 0                      # consecutive can't-admit iterations
        decode_failures = 0             # consecutive failed round attempts
        while True:
            # refill free slots from the queue between decode rounds; the
            # inner while re-admits into a slot whose request retired at
            # admission (max_new_tokens=1 / instant EOS / failed / already
            # past deadline). A paged admission the pool cannot back parks
            # its request in the FIFO holdback and stops refilling until
            # retirements release pages.
            blocked = False
            for i in range(B):
                while slots[i] is None and not blocked:
                    req = next_request()
                    if req is None:
                        break
                    if self._expired(req):
                        # expired while queued/parked: never admit — the
                        # prefill would be wasted work past the budget
                        retire_unadmitted(req, "timeout")
                        continue
                    if try_admit(i, req) == "full":
                        holdback.appendleft(req)
                        blocked = True
                    # "ok" / "failed": retired inside try_admit if it is
                    # done already — keep refilling
                if blocked:
                    break
            active = [i for i in range(B) if slots[i] is not None]
            if not active:
                if paged and holdback:
                    # can't-admit stall: nothing active to retire and the
                    # holdback head still does not fit. Give retirements
                    # max_retries+1 iterations to change the picture, then
                    # retire the head as "error" instead of deadlocking or
                    # killing the engine (the old RuntimeError) — the
                    # message keeps the kv_num_blocks diagnosis.
                    stalls += 1
                    if stalls > self.scfg.max_retries:
                        retire_unadmitted(
                            holdback.popleft(), "error",
                            "paged KV pool cannot admit this request even "
                            "with every page reclaimable — kv_num_blocks "
                            "is below its worst-case page need")
                        stalls = 0
                    continue
                break                   # the admit loop drained the queue
            stalls = 0
            if paged:
                with obs_trace.span("engine.upload") as sp:
                    sp.set(new_blocks=grow_tables())
                    active = [i for i in range(B) if slots[i] is not None]
                    if active:
                        cache["len"] = jnp.asarray(np.asarray(lens,
                                                              np.int32))
                        cache["block_table"] = jnp.asarray(table)
                self._m["upload_time"].inc(sp.seconds)
                if not active:
                    continue            # preemption emptied the batch
            try:
                # the seam fires BEFORE the device call (retrying an
                # injected fault is safe: the donated cache is untouched)
                round_fired = faults.check("engine.decode_round")
                t0 = time.perf_counter()
                with obs_trace.span("engine.decode_round",
                                    round=self._round,
                                    active=len(active)) as sp:
                    if paged:
                        pages = sum(lens[i] // bs + 1 for i in active)
                        sp.set(pages=pages)
                    ids, logits, cache = self.decode(
                        self.params, jnp.asarray(cur), cache)
                    # block on ALL outputs before stopping the timer:
                    # asarray alone would sync the ids but leave the
                    # cache update in flight, skewing decode_tok_s by JAX
                    # async dispatch
                    jax.block_until_ready((ids, logits, cache))
                    dt = time.perf_counter() - t0
                    if self._moe_counts:
                        self._count_experts(cache, sp)
                self._m["decode_time"].inc(dt)
                if paged:
                    self._m["kv_pages_read"].inc(pages)
            except Exception as e:
                retriable = isinstance(e, faults.InjectedFault)
                decode_failures += 1
                if retriable and decode_failures <= self.scfg.max_retries:
                    self._m["retries"].inc()
                    if self.scfg.retry_backoff_s > 0:
                        time.sleep(self.scfg.retry_backoff_s
                                   * (2 ** (decode_failures - 1)))
                    continue
                # unrecoverable round: the batch shares one donated cache,
                # so per-request attribution is impossible — retire the
                # whole active set as "error" and rebuild the arena, then
                # keep draining the queue against the fresh one
                for i in active:
                    retire_slot(i, "error", repr(e))
                rebuild_arena()
                decode_failures = 0
                continue
            decode_failures = 0
            with obs_trace.span("engine.sample", active=len(active)) as sp:
                if round_fired is not None:
                    # corrupt directive: poison this round's host logits;
                    # every active request sampled from them is contained,
                    # not fixed
                    rows = round_fired.apply(np.asarray(logits))[:, -1]
                    for i in active:
                        self.poisoned_uids.add(slots[i].uid)
                else:
                    # each row's pick was made on the device; a row's
                    # logits reach the host only if its request samples
                    ids = np.asarray(ids)
                    rows = {i: _DeviceRow(logits, i, int(ids[i]))
                            for i in active}
                self._round += 1
                self._m["decode_steps"].inc()
                self._m["occupied"].inc(len(active))
                now_r = time.perf_counter()
                for i in active:
                    lens[i] += 1        # this round wrote K/V at lens[i]
                    req = slots[i]
                    t = self._pick(rows[i], req)
                    req.out_tokens.append(t)
                    self._m["tokens_out"].inc()
                    cur[i, 0] = t
                    maybe_retire(i)
                    if slots[i] is not None and self._expired(req, now_r):
                        retire_slot(i, "timeout")   # round-boundary cancel
                host_rows = len(active) if round_fired is not None else \
                    sum(rows[i].fetched for i in active)
                sp.set(host_rows=host_rows)
                self._m["host_logit_rows"].inc(host_rows)
            self._m["sample_time"].inc(sp.seconds)
            # decode advanced every row's length, including retired/empty
            # slots; re-zero them so dead rows can never drift past max_len
            with obs_trace.span("engine.upload") as sp:
                cache["len"] = jnp.asarray(np.asarray(lens, np.int32))
            self._m["upload_time"].inc(sp.seconds)
        return finished

    # ------------------------------------------------------------- static --

    def _run_static(self) -> List[Request]:
        finished: List[Request] = []
        while True:
            batch = self._take_batch()
            if not batch:
                break
            finished.extend(self._run_batch(batch))
        return finished

    def _retry_call(self, site: str, fn):
        """Run ``fn`` behind fault-site ``site`` with bounded retries.
        Returns ``(result, fired, err)``: on success err is None and fired
        is the corrupt directive (if one fired); after exhausting
        ``max_retries`` (InjectedFault only — a real exception may have
        consumed donated buffers, so it never retries) result is None and
        err carries the absorbed exception."""
        last_err: Optional[BaseException] = None
        for attempt in range(self.scfg.max_retries + 1):
            if attempt:
                self._m["retries"].inc()
                if self.scfg.retry_backoff_s > 0:
                    time.sleep(self.scfg.retry_backoff_s
                               * (2 ** (attempt - 1)))
            try:
                fired = faults.check(site)
                return fn(), fired, None
            except faults.InjectedFault as e:
                last_err = e
                continue
            except Exception as e:
                last_err = e
                break
        return None, None, last_err

    def _run_batch(self, reqs: List[Request]) -> List[Request]:
        b = len(reqs)
        plen = max(len(r.prompt) for r in reqs)
        toks = np.zeros((b, plen), np.int32)
        for i, r in enumerate(reqs):
            toks[i, plen - len(r.prompt):] = r.prompt      # left-pad
        now = time.perf_counter()
        for r in reqs:
            r.admit_t = now

        def do_prefill():
            with obs_trace.span("engine.prefill", batch=b, plen=plen):
                logits, cache = self.prefill(self.params,
                                             {"tokens": jnp.asarray(toks)})
                self._m["prefills"].inc()
                return np.asarray(logits), cache

        got, fired, err = self._retry_call("engine.prefill", do_prefill)
        if err is not None:
            # the static batch shares one prefill: retire it whole — the
            # next _take_batch keeps draining the queue
            for r in reqs:
                r.status = "error"
                r.error = repr(err)
                r.done = True
                self._m["errors"].inc()
            return self._finish_batch(reqs)
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
                    r.done = True       # round-boundary cancellation
                    r.status = "timeout"
                    self._m["timeouts"].inc()
            if all(r.done for r in reqs):
                break

            def do_round():
                t0 = time.perf_counter()
                with obs_trace.span("engine.decode_round",
                                    round=self._round,
                                    active=sum(not r.done for r in reqs)):
                    _, logits, new_cache = self.decode(
                        self.params, jnp.asarray(cur), cache)
                    # sync logits AND cache before stopping the timer (see
                    # the continuous path): decode_tok_s must be device
                    # time
                    jax.block_until_ready((logits, new_cache))
                self._m["decode_time"].inc(time.perf_counter() - t0)
                return np.asarray(logits), new_cache

            got, fired, err = self._retry_call("engine.decode_round",
                                               do_round)
            if err is not None:
                for r in reqs:          # one shared (donated) cache: no
                    if not r.done:      # per-request attribution possible
                        r.status = "error"
                        r.error = repr(err)
                        r.done = True
                        self._m["errors"].inc()
                break
            lg, cache = got
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
        return self._finish_batch(reqs)

    def _finish_batch(self, reqs: List[Request]) -> List[Request]:
        now = time.perf_counter()
        for r in reqs:
            r.done = True
            if r.status == "pending":
                r.status = "ok"
            if r.first_token_t == 0.0:  # batch failed before first token
                r.first_token_t = now
            r.finish_t = now
            r.finish_round = self._round
            self._m["requests_done"].inc()
            self._observe_retired(r)
        return reqs
