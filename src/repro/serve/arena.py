"""The continuous scheduler's KV arena: a decode batch's ``max_batch``
slots and the cache that backs them.

``Engine`` builds one per drain (:func:`make_arena`, from
``ServeConfig.kv_layout``), and a fresh one after a decode round fails
for good. Its loop drives either layout through the same calls and never
asks which one it holds: ``admit(i, req, sp)`` (prefill ``req`` into free
slot ``i``; the host logits, or None when a paged pool cannot back the
prompt), ``release(i)``, ``prepare()`` before a round (page growth, which
yields whenever the pool cannot back a slot so the engine can preempt
one, and the uploads), ``pages(active)`` (what the round's rows read) and
``upload_len()`` after it. The arena also holds the per-slot state both
layouts share: each slot's request, ``lens`` (the host mirror of
``cache["len"]``), ``cur`` (its next input token) and ``order``
(occupied slots, oldest admission first). Its device calls are the
engine's jitted callables, built once in ``Engine.__init__``: a
``jax.jit`` here would compile anew on every drain. Each call that takes
the cache donates it, so the arena always replaces its ``cache``.

* :class:`SlotArena` (``"contiguous"``): every slot owns a (max_len,) KV
  row of one batched cache, ``max_batch x max_len`` positions whatever
  the sequences' lengths. The only layout of the ssm and hybrid families.
* :class:`PagedArena` (``"paged"``): K/V live in a shared
  :class:`BlockPool` of ``kv_block_size``-position pages; a slot's block
  table grows a page at a time, so resident KV scales with actual tokens.
  Requests that share a prompt prefix map their leading table entries to
  the same refcounted pages: on a float-KV hit only the suffix runs
  through the model (``models/api.prefill_suffix_fn``); an int8-KV hit
  shares storage only (dequantized codes are not the float prefix, so the
  prompt is recomputed and the shared-page writes skipped). Greedy
  streams match the contiguous layout exactly for int8 pools; float pools
  decode through the ``paged_attention`` kernel, whose online softmax
  rounds differently. A pattern stack's short convolutions keep a
  per-slot state beside the pages (``models/api.slot_state_axes``),
  written at admission and cleared at release; its prefix cache is
  refused, since a hit would need that state at the page boundary.
"""
from __future__ import annotations

import collections
import functools
import hashlib
import time
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.check.config import paged_num_blocks
from repro.faults import inject as faults
from repro.models import api
from repro.obs import trace as obs_trace


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


class Arena:
    """What both layouts keep per slot; the layout's own class adds the
    cache and the calls the engine's loop makes."""
    pool: Optional[BlockPool] = None

    def __init__(self, eng, cache: dict):
        B = eng.scfg.max_batch
        self.eng = eng
        self.cache = cache
        self.reqs: list = [None] * B    # the request each slot holds
        self.lens = [0] * B             # host mirror of cache["len"]
        self.cur = np.zeros((B, 1), np.int32)   # each slot's next token
        self.order: List[int] = []      # occupied, oldest admission first

    def _occupy(self, i: int, req, plen: int):
        self.reqs[i], self.lens[i] = req, plen
        self.order.append(i)

    def release(self, i: int):
        """Take slot ``i``'s request out (returned) and free its KV: the
        zeroed length makes the slot's stale K/V unreachable."""
        req, self.reqs[i], self.lens[i] = self.reqs[i], None, 0
        self.order.remove(i)
        self.cache = api.cache_free_slot(self.cache, i)
        return req

    def prepare(self):
        """Nothing to do before a round: admission wrote each slot's
        length with its KV, and ``upload_len`` the rest."""
        return ()

    def pages(self, active: List[int]) -> int:
        return 0

    def upload_len(self):
        """``lens`` to the device as ``cache["len"]``. After a round this
        re-zeroes the empty slots' rows, which decode advanced too, so a
        dead row never drifts past max_len."""
        self.cache["len"] = jnp.asarray(np.asarray(self.lens, np.int32))


class SlotArena(Arena):
    """``kv_layout="contiguous"``: one (max_len,) KV row per slot."""

    def __init__(self, eng):
        s = eng.scfg
        super().__init__(eng, api.init_slot_cache(
            eng.cfg, s.max_batch, s.max_len, kv=s.kv_cache))

    def admit(self, i: int, req, sp):
        """Bucketed prefill of the whole prompt, written into slot ``i``."""
        plen = len(req.prompt)
        req.admit_t = time.perf_counter()
        toks = self.eng._padded(req.prompt)
        logits, _ = self.eng._prefill(
            toks, [plen], functools.partial(self._write, i),
            uid=req.uid, slot=i, plen=plen, bucket=toks.shape[1])
        self._occupy(i, req, plen)
        return logits

    def _write(self, i: int, fresh: dict):
        self.cache = self.eng._write_slot(self.cache, fresh, jnp.int32(i))


class PagedArena(Arena):
    """``kv_layout="paged"``: a :class:`BlockPool`, each slot's pages and
    the block table that maps them, uploaded before every round."""

    def __init__(self, eng):
        s = eng.scfg
        B, bs = s.max_batch, s.kv_block_size
        nblocks = paged_num_blocks(s)
        super().__init__(eng, api.init_paged_cache(
            eng.cfg, B, nblocks, bs, s.max_len, kv=s.kv_cache))
        self.bs = bs
        self.pool = BlockPool(nblocks, bs, prefix_cache=s.prefix_cache)
        self.table = np.zeros((B, s.max_len // bs), np.int32)
        self.ids: List[List[int]] = [[] for _ in range(B)]  # slot's pages
        self.hashed = [0] * B           # leading refcounted pages per slot
        # a hit's shared pages hold the float prefix K/V that the
        # suffix-only prefill reads; int8 codes are not that prefix
        self.suffix_hits = s.kv_cache == "float"
        self._gauges()

    def _gauges(self):
        m, pool = self.eng._m, self.pool
        m["blocks_in_use"].set(pool.in_use)
        m["blocks_free"].set(pool.free_pages)
        m["prefix_hit_rate"].set(
            pool.hits / pool.lookups if pool.lookups else 0.0)

    def admit(self, i: int, req, sp):
        """Look up the prompt's prefix pages, reference the hits, allocate
        the rest and prefill; None when the pool cannot supply the pages.
        ``sp`` (the ``engine.admit`` span) gets the page counts. A failure
        after pages were referenced or allocated rolls the pool back
        before it re-raises, so a retried or retired admission leaks no
        page."""
        pool, bs = self.pool, self.bs
        plen = len(req.prompt)
        nb = -(-plen // bs)             # pages covering positions [0, plen)
        keys = pool.prefix_keys(req.prompt)
        hit = pool.lookup(keys)
        n_hit = len(hit)
        sp.set(hit_blocks=n_hit, new_blocks=nb - n_hit)
        # reference the hit pages BEFORE alloc so its eviction scan
        # cannot reclaim them out from under this admission
        pool.acquire(hit)
        fresh: List[int] = []
        try:
            got = pool.alloc(nb - n_hit)
            if got is None:
                pool.release(hit)
                return None
            fresh = got
            req.admit_t = time.perf_counter()
            logits = self._prefill(i, req, hit, fresh)
        except Exception:
            pool.release(hit)
            pool.free(fresh)            # unpublished: straight back
            self._gauges()
            raise
        ids = hit + fresh
        # publish-at-admission: the fresh full prompt pages now hold their
        # final K/V (decode writes land strictly past them)
        pool.publish(keys[n_hit:], ids[n_hit:len(keys)])
        self.ids[i], self.hashed[i] = ids, len(keys)
        self.table[i, :nb] = ids
        self.table[i, nb:] = 0
        self._gauges()
        self._occupy(i, req, plen)
        return logits

    def _prefill(self, i: int, req, hit: List[int], fresh: List[int]):
        eng = self.eng
        plen = len(req.prompt)
        pfx = len(hit) * self.bs
        fids = np.asarray(fresh, np.int32)
        if hit and self.suffix_hits:
            # the shared pages already hold the prefix K/V: gather them
            # and run ONLY the suffix
            toks = eng._padded(req.prompt[pfx:])
            with obs_trace.span("engine.prefill", uid=req.uid, slot=i,
                                plen=plen, bucket=toks.shape[1],
                                prefix_hit=pfx):
                pk, pv = eng._gather_prefix(self.cache,
                                            np.asarray(hit, np.int32))
                logits, ks, vs = eng.prefill_suffix(eng.params, {
                    "tokens": jnp.asarray(toks),
                    "prefix_k": pk, "prefix_v": pv,
                    "suffix_lens": jnp.asarray([plen - pfx], jnp.int32)})
                eng._m["prefills"].inc()
                self.cache = eng._write_kv(self.cache, ks, vs, fids)
                return np.asarray(logits)
        # a miss, or an int8-KV hit (shared storage only): the whole
        # prompt, writing all but the shared pages
        toks = eng._padded(req.prompt)
        logits, _ = eng._prefill(
            toks, [plen], functools.partial(self._write, i, fids, len(hit)),
            uid=req.uid, slot=i, plen=plen, bucket=toks.shape[1],
            prefix_hit=pfx)
        return logits

    def _write(self, i: int, fids, skip: int, fresh: dict):
        eng = self.eng
        self.cache = eng._write_pages(self.cache, fresh, fids,
                                      skip_blocks=skip)
        if eng._write_state is not None:
            self.cache = eng._write_state(self.cache, fresh, jnp.int32(i))

    def release(self, i: int):
        self.pool.free(self.ids[i], hashed=self.hashed[i])
        self.ids[i], self.hashed[i] = [], 0
        self.table[i] = 0
        self._gauges()
        return super().release(i)

    def _alloc(self) -> Optional[int]:
        """One page, or None when the pool is dry. An injected
        ``blockpool.alloc`` fault counts as a retry and reads as a
        shortage, which growth already answers, so it never escapes
        mid-decode."""
        try:
            got = self.pool.alloc(1)
        except faults.InjectedFault:
            self.eng._m["retries"].inc()
            return None
        return got[0] if got else None

    def prepare(self):
        """One ``engine.upload`` span: give every slot whose next write
        position starts a page that page, oldest admission first, then
        upload ``len`` and the block table. Each time the pool cannot
        supply a page this yields, and the caller frees pages by releasing
        a slot; the walk skips released slots. Walking oldest first
        guarantees progress: a lone grower can always reclaim evictable
        pages."""
        eng, bs, max_len = self.eng, self.bs, self.eng.scfg.max_len
        with obs_trace.span("engine.upload") as sp:
            grown = 0
            for i in list(self.order):
                pos = self.lens[i]
                if self.reqs[i] is None or pos >= max_len or pos % bs \
                        or pos // bs < len(self.ids[i]):
                    continue
                page = self._alloc()
                while page is None:
                    yield i
                    if self.reqs[i] is None:
                        break
                    page = self._alloc()
                if page is not None:
                    self.ids[i].append(page)
                    self.table[i, pos // bs] = page
                    grown += 1
            self._gauges()
            sp.set(new_blocks=grown)
            if self.order:
                self.upload_len()
                self.cache["block_table"] = jnp.asarray(self.table)
        eng._m["upload_time"].inc(sp.seconds)

    def pages(self, active: List[int]) -> int:
        """KV pages per layer the round's rows hold, each row's new
        position included: what the paged attention reads."""
        return sum(self.lens[i] // self.bs + 1 for i in active)


def make_arena(eng) -> Arena:
    """The arena ``eng.scfg.kv_layout`` names, empty."""
    return (PagedArena if eng.scfg.kv_layout == "paged" else SlotArena)(eng)
