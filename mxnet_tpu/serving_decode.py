"""Continuous-batching autoregressive serving: decode-step programs +
paged KV-cache + multi-model SLO-aware admission.

``serving.py`` (PR 4) bounds the program set for *one-shot* inference:
pad to a bucket, dispatch, slice.  Autoregressive generation breaks that
model — a request is hundreds of sequential dispatches over a growing
sequence, and batching whole requests leaves the chip idle whenever the
longest member is still decoding.  This module is the generative analog,
built on three ideas:

1. **A bounded program set** (the fusion-boundary lesson of
   arXiv:2301.13062): per-token work is ONE fused XLA decode program —
   fixed row capacity ``MXNET_SERVE_DECODE_ROWS``, page-table-indexed
   KV gather, attention, token sample, and the KV scatter all inside the
   same jit — plus one prefill program per PR-4 sequence-length bucket
   (:class:`serving.BucketPolicy` generalized along the sequence axis).
   Programs live in the ProgramStore ``serving_decode`` namespace and
   :meth:`GenerativeEngine.warmup` compiles the whole grid from abstract
   shapes at deploy time.  Steady state: 0 retraces, 1 dispatch per
   generated token-batch.

2. **Paged KV-cache** (:class:`PagePool`): the cache is a fixed HBM pool
   of ``MXNET_KV_PAGES`` pages of ``MXNET_KV_PAGE`` tokens each
   (donated to every prefill/decode dispatch, so it updates in place off
   the host path).  A sequence holds ``ceil(len/page)`` pages via a
   page table and releases them the iteration it retires — no
   max-length pre-reservation, so memory scales with *live tokens*, not
   worst-case length.  **Continuous batching**: the scheduler admits
   newly-arrived prefills into freed rows and retires finished
   sequences every iteration; the decode program always runs full
   width with dead rows masked (their KV writes land in a reserved
   trash page), so join/retire never changes a shape.

3. **Multi-model + SLO-aware admission**: N :class:`GenerativeEngine`\\ s
   per process share the page pool (:func:`shared_pool`) — the
   cross-model HBM budget — while ProgramStore caps stay per-owner
   (PR 7), so a co-hosted model can never evict a neighbor's decode
   program.  Admission is **cost-table driven** (the
   arXiv:2008.01040 move: predict, don't trial-dispatch): a per-bucket
   EMA of measured prefill/decode-step times prices each request, and a
   request that cannot meet ``MXNET_SERVE_SLO_US`` — or arrives past
   ``MXNET_SERVE_MAX_QUEUE``, or needs more pages than the pool has —
   is refused *immediately* with the typed :class:`faults.ShedError`
   (site ``serving.admit``), never parked toward a timeout.  Pool
   exhaustion mid-decode preempts the youngest sequence (pages freed,
   request re-queued; greedy decoding makes the recomputed continuation
   token-exact).  Per-model p50/p99, SLO-violation, shed, and preempt
   counters land in :meth:`GenerativeEngine.stats`.

4. **Content-addressed prefix cache** (``MXNET_PREFIX_CACHE``, default
   on): every prompt page is keyed by a rolling hash of its token
   block, chain-hashed so a block's key commits to its FULL prefix.
   N requests sharing a prompt reference one physical prefill —
   pages are refcounted, admission looks the chain up and prefills
   only the uncached suffix (one dispatch from the first miss block;
   the page table already gathers by index, so decode is untouched) —
   and fork copy-on-write at the first divergent KV write.  Pages
   whose refcount drops to zero stay resident as an LRU cache;
   ``alloc`` evicts them under pressure and raises
   :class:`PagePoolExhausted` only when even eviction cannot help.
   Whether a prefix is worth hashing at all is a cost-table decision
   (measured probe EMA vs the measured per-block prefill EMA — the
   arXiv:2008.01040 move again).  Counters: ``prefix.hit_blocks`` /
   ``prefix.miss_blocks`` / ``prefix.cow_forks`` /
   ``prefix.evictions``; hit rate rides the prefill trace events.

The dispatch-budget gate (``tools/check_dispatch_budget.py`` ``decode``
lane) pins the contract: live programs == prefill buckets + 1, 0
retraces and 1 dispatch per decode iteration across a join/retire
storm, 0 leaked pages after drain.
"""
from __future__ import annotations

import hashlib
import heapq
import math
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from . import config as _config
from . import faults as _faults
from . import preemption as _preemption
from . import program_store as _pstore
from . import telemetry as _telemetry
from .context import current_context
from .faults import ShedError
from .serving import BucketPolicy

__all__ = ["PagePool", "PagePoolExhausted", "ShedError", "DecodeModel",
           "TinyCausalLM", "GenerativeEngine", "shared_pool",
           "eager_generate", "trace_count", "dispatch_count",
           "reset_counters", "SamplingSpec", "sample_token",
           "spec_trace_count", "spec_dispatch_count",
           "high_agreement_pair"]

_NS = _pstore.namespace("serving_decode")
# speculative-decoding programs (draft prefill / draft round / verify)
# live in their OWN namespace so the dispatch-budget spec lane can pin
# "programs == draft buckets + verify shapes + 1" and "0 spec
# dispatches with MXNET_SPEC_DECODE=0" independently of the plain
# decode budget
_SPEC_NS = _pstore.namespace("serving_spec")


def trace_count() -> int:
    return _NS.traces


def dispatch_count() -> int:
    return _NS.dispatches


def spec_trace_count() -> int:
    return _SPEC_NS.traces


def spec_dispatch_count() -> int:
    return _SPEC_NS.dispatches


def reset_counters() -> None:
    _NS.reset()
    _SPEC_NS.reset()


class PagePoolExhausted(ShedError):
    """No free KV-cache pages — the typed refusal admission raises and
    the scheduler's preemption path absorbs."""

    kind = "pool"


class _DispatchGate:
    """SLO-aware dispatch ordering across the engines sharing one pool
    (i.e. one device budget): each prefill/decode dispatch acquires the
    gate with a priority (the engine's SLO; ``inf`` when unset), and
    waiters are served most-urgent-first, FIFO on ties.  Without it a
    slow co-tenant's free-running decode loop issues steps back to
    back and a fast model's p99 is unbounded by anything but luck;
    with it a fast step waits for AT MOST one in-flight slow step —
    the multi-model interference bound the storm bench measures."""

    def __init__(self):
        self._cv = threading.Condition()
        self._busy = False
        self._seq = 0
        self._heap: List[Tuple[float, int]] = []

    def acquire(self, priority: float) -> None:
        with self._cv:
            self._seq += 1
            tok = (priority, self._seq)
            heapq.heappush(self._heap, tok)
            while self._busy or self._heap[0] != tok:
                self._cv.wait()
            heapq.heappop(self._heap)
            self._busy = True

    def release(self) -> None:
        with self._cv:
            self._busy = False
            self._cv.notify_all()


# ---------------------------------------------------------------------------
# Content-addressed prefix cache (hash-chained page keys)
# ---------------------------------------------------------------------------
# process-global counters (family 'prefix'): sharing is a cross-pool
# property of the workload, so unlike the per-instance kv_pool group
# these are NOT instance-numbered — telemetry.merge sums them across
# the fleet under exact names
_PREFIX_STATS = _telemetry.CounterGroup(
    "prefix", ("hit_blocks", "miss_blocks", "cow_forks", "evictions"),
    doc="content-addressed KV prefix cache (MXNET_PREFIX_CACHE)",
    family="prefix")

# speculative-decoding counters (family 'spec'): like prefix sharing,
# acceptance is a property of the model PAIR and the workload, so the
# family is process-global (not instance-numbered) — telemetry.merge
# sums it across the fleet under exact names.
# rounds = spec rounds completed (1 draft + 1 verify dispatch each);
# proposed/accepted = draft tokens offered / surviving rejection
# sampling; fallback_rounds = rounds the arbiter declined (cost table
# said plain decode is cheaper, or shapes/pages did not fit);
# autodisabled = sticky low-acceptance cutoffs (the poisoned-draft
# degrade path)
_SPEC_STATS = _telemetry.CounterGroup(
    "spec", ("rounds", "proposed", "accepted", "fallback_rounds",
             "autodisabled"),
    doc="speculative decoding (MXNET_SPEC_DECODE)", family="spec")

# measured acceptance and amortization ride as computed gauges over the
# same counters: acceptance_rate = accepted /
# proposed; tokens_per_target_dispatch = (accepted + rounds) / rounds
# (each round costs ONE target-equivalent verify dispatch and yields
# n_acc + 1 tokens) — the k-for-1 number the tentpole is judged on
_telemetry.gauge_fn(
    "spec.acceptance_rate",
    lambda: (_SPEC_STATS["accepted"] / _SPEC_STATS["proposed"]
             if _SPEC_STATS["proposed"] else 0.0),
    doc="speculative decoding: fraction of drafted tokens accepted",
    family="spec")
_telemetry.gauge_fn(
    "spec.tokens_per_target_dispatch",
    lambda: ((_SPEC_STATS["accepted"] + _SPEC_STATS["rounds"])
             / _SPEC_STATS["rounds"] if _SPEC_STATS["rounds"] else 0.0),
    doc="speculative decoding: tokens committed per verify dispatch",
    family="spec")


def _chain_keys(tokens: Sequence[int], page: int,
                geom: Tuple) -> List[bytes]:
    """Rolling content keys, one per ``page``-token block of
    ``tokens`` (the last block may be partial).  Key ``i`` is
    ``blake2b(key[i-1] || block_i)`` seeded with the KV geometry, so a
    key commits to the ENTIRE token prefix through its block AND to the
    storage layout — equal keys imply byte-equal cached KV, across
    models only when their geometry genuinely matches."""
    prev = repr((geom, page)).encode()
    keys: List[bytes] = []
    for i in range(0, len(tokens), page):
        h = hashlib.blake2b(prev, digest_size=16)
        h.update(onp.asarray(tokens[i:i + page], onp.int64)  # graftlint: disable=host-sync -- hashing Python token ids host-side; no device buffer is read
                 .tobytes())
        prev = h.digest()
        keys.append(prev)
    return keys


# ---------------------------------------------------------------------------
# Paged KV-cache pool
# ---------------------------------------------------------------------------
class PagePool:
    """Fixed pool of KV-cache pages shared by every engine in the
    process.

    Accounting is GLOBAL (one free list of ``pages`` page ids — the
    scheduling resource all co-hosted models contend for); storage is
    per KV *geometry* ``(n_layers, n_heads, head_dim, dtype)``: each
    registered geometry owns a ``(pages+1, page, L, H, D)`` key array
    and value array, where index ``pages`` is the reserved TRASH page
    masked rows and pad positions write into.  Engines sharing a
    geometry share storage, so their dispatches serialize through
    :meth:`exclusive` (the pool buffers are donated); distinct
    geometries run concurrently.

    ``alloc`` raises :class:`PagePoolExhausted` (a typed
    :class:`faults.ShedError`) instead of blocking — the caller decides
    between shedding (admission) and preempting (mid-decode).
    """

    def __init__(self, pages: Optional[int] = None,
                 page: Optional[int] = None):
        self.page = int(page if page is not None
                        else _config.get("MXNET_KV_PAGE"))
        self.pages = int(pages if pages is not None
                         else _config.get("MXNET_KV_PAGES"))
        if self.page < 1 or self.pages < 1:
            raise ValueError(
                f"PagePool needs pages>=1, page>=1 (got {self.pages}, "
                f"{self.page})")
        # LIFO free list: a just-freed (hot-in-HBM) page is reused first
        self._free: List[int] = list(range(self.pages - 1, -1, -1))
        self._in_use: set = set()
        # content-addressed prefix cache (MXNET_PREFIX_CACHE): pages are
        # refcounted; a page whose refcount drops to 0 while it still
        # holds published (chain-keyed) content parks in ``_lru``
        # instead of the free list — resident cache, reclaimed
        # oldest-first by ``alloc`` under pressure
        self._refs: Dict[int, int] = {}
        self._index: Dict[Tuple, Dict[bytes, int]] = {}  # geom -> key -> page
        self._page_key: Dict[int, Tuple[Tuple, bytes]] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._lock = threading.Lock()
        self._storage: Dict[Tuple, List] = {}        # geom -> [k, v]
        self._geom_locks: Dict[Tuple, threading.RLock] = {}
        self.gate = _DispatchGate()
        # pool accounting lives in the telemetry registry (family
        # 'kv_pool'); the alloc_count/... properties below keep the
        # attribute reads working
        self._counts = _telemetry.CounterGroup(
            _telemetry.instance_name("kv_pool"),
            ("alloc", "free", "exhausted"),
            doc="paged KV-cache pool page accounting", family="kv_pool")
        self.high_water = 0

    @property
    def alloc_count(self) -> int:
        return self._counts["alloc"]

    @property
    def free_count(self) -> int:
        return self._counts["free"]

    @property
    def exhausted_count(self) -> int:
        return self._counts["exhausted"]

    @property
    def trash(self) -> int:
        """The reserved scratch page index (== ``pages``): dead decode
        rows and prefill pad positions scatter here; it is never
        allocated and never read unmasked."""
        return self.pages

    # -- accounting --------------------------------------------------------
    # Accounting is by REFERENCE: ``alloc`` and a prefix-cache hit both
    # acquire one reference per page (counted 'alloc'); ``free``
    # releases one (counted 'free'), so alloc_count - free_count ==
    # live references even when pages are shared.
    def _evict_locked(self, n: int) -> None:
        """Reclaim ``n`` cached-but-unreferenced pages (oldest first)
        onto the free list.  Caller holds ``_lock`` and has checked
        ``len(self._lru) >= n``.  Only LRU residents are ever evicted —
        a referenced page (refcount >= 1) is never reclaimed."""
        for _ in range(n):
            p, _ = self._lru.popitem(last=False)
            geom, key = self._page_key.pop(p)
            self._index[geom].pop(key, None)
            self._free.append(p)
            _PREFIX_STATS.inc("evictions")

    def alloc(self, n: int) -> List[int]:
        with self._lock:
            short = n - len(self._free)
            if short > len(self._lru):
                self._counts.inc("exhausted")
                raise PagePoolExhausted(
                    f"KV page pool exhausted: need {n} page(s), "
                    f"{len(self._free)} free + {len(self._lru)} "
                    f"evictable of {self.pages} "
                    f"(page={self.page} tokens)")
            if short > 0:
                self._evict_locked(short)
            got = [self._free.pop() for _ in range(n)]
            self._in_use.update(got)
            for p in got:
                self._refs[p] = 1
            self._counts.inc("alloc", n)
            self.high_water = max(self.high_water, len(self._in_use))
            return got

    def free(self, pages: Sequence[int]) -> None:
        """Release one REFERENCE per page.  A page still shared stays
        in use; an unreferenced page returns to the free list — unless
        it holds published prefix content, in which case it parks in
        the resident LRU cache (still reclaimable, never leaked:
        ``in_use()`` counts references only)."""
        with self._lock:
            for p in pages:
                if p not in self._in_use:
                    raise ValueError(
                        f"double/foreign free of page {p} (in_use="
                        f"{len(self._in_use)})")
                self._counts.inc("free")
                self._refs[p] -= 1
                if self._refs[p] > 0:
                    continue
                del self._refs[p]
                self._in_use.discard(p)
                if p in self._page_key:
                    self._lru[p] = None     # newest at the MRU end
                else:
                    self._free.append(p)

    def in_use(self) -> int:
        with self._lock:
            return len(self._in_use)

    def free_pages(self) -> int:
        """Allocatable pages: truly free plus cached-but-unreferenced
        (one eviction away from free) — the number ``alloc`` can
        satisfy without preempting anyone."""
        with self._lock:
            return len(self._free) + len(self._lru)

    def ref(self, p: int) -> int:
        """Current reference count of page ``p`` (0 = free or cached)."""
        with self._lock:
            return self._refs.get(p, 0)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"pages": self.pages, "page": self.page,
                    "in_use": len(self._in_use),
                    "free": len(self._free),
                    "cached": len(self._lru),
                    "alloc_count": self.alloc_count,
                    "free_count": self.free_count,
                    "exhausted_count": self.exhausted_count,
                    "high_water": self.high_water}

    # -- content-addressed prefix cache ------------------------------------
    def lookup(self, geom: Tuple, keys: Sequence[bytes]) -> List[int]:
        """Longest cached prefix of the hash chain ``keys``: walks the
        chain in order, ACQUIRES one reference per hit page (an LRU
        resident revives to refcount 1), and stops at the first miss.
        Returns the hit pages in chain order; counts hit/miss blocks."""
        hits: List[int] = []
        with self._lock:
            idx = self._index.get(geom, {})
            for key in keys:
                p = idx.get(key)
                if p is None:
                    break
                if p in self._in_use:
                    self._refs[p] += 1
                else:
                    self._lru.pop(p)
                    self._in_use.add(p)
                    self._refs[p] = 1
                self._counts.inc("alloc")
                self.high_water = max(self.high_water,
                                      len(self._in_use))
                hits.append(p)
        _PREFIX_STATS.inc("hit_blocks", len(hits))
        _PREFIX_STATS.inc("miss_blocks", len(keys) - len(hits))
        return hits

    def publish(self, geom: Tuple,
                entries: Sequence[Tuple[bytes, int]]) -> None:
        """Register freshly-prefilled pages under their chain keys.
        First writer wins: a key already mapping to a live page keeps
        its mapping and the duplicate page simply stays private (it
        frees normally, it just can never be hit)."""
        with self._lock:
            idx = self._index.setdefault(geom, {})
            for key, p in entries:
                if key in idx or p in self._page_key:
                    continue
                if p not in self._in_use:
                    raise ValueError(
                        f"publish of page {p} which is not in use")
                idx[key] = p
                self._page_key[p] = (geom, key)

    def holds(self, geom: Tuple, keys: Sequence[bytes]) -> int:
        """Router affinity probe: how many LEADING blocks of the chain
        are resident (referenced or cached).  No reference bump, no
        recency update, no device work."""
        with self._lock:
            idx = self._index.get(geom)
            if not idx:
                return 0
            n = 0
            for key in keys:
                if key not in idx:
                    break
                n += 1
            return n

    def shared(self, p: int) -> bool:
        """True when writing page ``p`` needs a copy-on-write fork
        first: another row also references it, or it is published
        content a future lookup may still hit.  Content-addressed
        pages are IMMUTABLE — a row never scatters into a page anyone
        else can read."""
        with self._lock:
            return self._refs.get(p, 0) > 1 or p in self._page_key

    def fork(self, geom: Tuple, p: int) -> int:
        """Copy-on-write: allocate a private copy of shared page ``p``
        (device-side K/V copy under the geometry's exclusive lock),
        release this caller's reference on ``p``, and return the new
        page id.  May evict / raise :class:`PagePoolExhausted` exactly
        like ``alloc``."""
        new = self.alloc(1)[0]
        with self.exclusive(geom):
            k, v = self._storage[geom]
            self._storage[geom] = [k.at[new].set(k[p]),
                                   v.at[new].set(v[p])]
        self.free([p])
        _PREFIX_STATS.inc("cow_forks")
        return new

    def clear_prefix_cache(self) -> int:
        """Drop every cached-but-unreferenced page back to the free
        list and unpublish all content keys (cold-cache A/B runs, test
        isolation).  Live pages keep their references; they just stop
        being discoverable.  Returns pages reclaimed."""
        with self._lock:
            reclaimed = len(self._lru)
            for p in self._lru:
                self._free.append(p)
            self._lru.clear()
            self._index.clear()
            self._page_key.clear()
            return reclaimed

    def audit(self) -> List[str]:
        """Refcount/bookkeeping invariant check (drills run it at
        drain): returns violation strings, [] when sound."""
        bad: List[str] = []
        with self._lock:
            if set(self._refs) != self._in_use:
                bad.append(f"refs/in_use mismatch: {sorted(self._refs)}"
                           f" vs {sorted(self._in_use)}")
            for p, r in self._refs.items():
                if r < 1:
                    bad.append(f"page {p} in use with refcount {r}")
            free, lru = set(self._free), set(self._lru)
            if free & lru:
                bad.append(f"pages both free and cached: {free & lru}")
            if free & self._in_use or lru & self._in_use:
                bad.append("pages both free/cached and in use: "
                           f"{(free | lru) & self._in_use}")
            total = len(self._free) + len(self._lru) + len(self._in_use)
            if total != self.pages:
                bad.append(f"page conservation broke: {len(self._free)}"
                           f" free + {len(self._lru)} cached + "
                           f"{len(self._in_use)} in use != {self.pages}")
            for geom, idx in self._index.items():
                for key, p in idx.items():
                    if self._page_key.get(p) != (geom, key):
                        bad.append(f"index key {key.hex()} -> page {p} "
                                   "lacks its reverse mapping")
                    if p not in self._in_use and p not in lru:
                        bad.append(f"index key {key.hex()} -> page {p} "
                                   "which is neither live nor cached")
        return bad

    # -- storage -----------------------------------------------------------
    def register(self, n_layers: int, n_heads: int, head_dim: int,
                 dtype=jnp.float32) -> Tuple:
        """Declare a KV geometry; allocates its (pages+1)-page K and V
        arrays on first sight.  Returns the storage key."""
        geom = (int(n_layers), int(n_heads), int(head_dim),
                jnp.dtype(dtype).name)
        with self._lock:
            if geom not in self._storage:
                shape = (self.pages + 1, self.page, geom[0], geom[1],
                         geom[2])
                # committed to the default context's device: an
                # uncommitted buffer would follow whatever operand
                # happens to be committed elsewhere
                dev = current_context().jax_device
                self._storage[geom] = [
                    jax.device_put(jnp.zeros(shape, dtype=dtype), dev),
                    jax.device_put(jnp.zeros(shape, dtype=dtype), dev)]
                self._geom_locks[geom] = threading.RLock()
        return geom

    def exclusive(self, geom: Tuple) -> threading.RLock:
        """The per-geometry dispatch lock: every program that consumes
        (donates) this geometry's buffers must hold it across
        dispatch + storage swap."""
        return self._geom_locks[geom]

    def storage(self, geom: Tuple) -> Tuple:
        k, v = self._storage[geom]
        return k, v

    def set_storage(self, geom: Tuple, k, v) -> None:
        self._storage[geom][0] = k
        self._storage[geom][1] = v

    # -- test hook ---------------------------------------------------------
    def poison_free(self, value: float = 1e30) -> int:
        """Overwrite every FREE page (all geometries) with ``value`` —
        the aliasing canary: if any live sequence ever reads a page it
        does not own, its next tokens diverge loudly.  Returns the
        number of pages poisoned.  The free list is read UNDER the
        geometry's dispatch lock: a page taken from it before the lock
        could be allocated and written by a decode step in between, and
        the poison would then land on live keys and values."""
        with self._lock:
            geoms = list(self._storage)
        poisoned = 0
        for g in geoms:
            with self.exclusive(g):
                with self._lock:
                    free = list(self._free)
                if not free:
                    continue
                idx = jnp.asarray(free, jnp.int32)
                k, v = self._storage[g]
                self._storage[g] = [k.at[idx].set(value),
                                    v.at[idx].set(value)]
                poisoned = max(poisoned, len(free))
        return poisoned


_SHARED: Optional[PagePool] = None
_SHARED_LOCK = threading.Lock()


def shared_pool() -> PagePool:
    """The process-shared pool every engine defaults to — the one HBM
    budget co-hosted models contend for (sized by ``MXNET_KV_PAGES`` /
    ``MXNET_KV_PAGE`` at first use)."""
    global _SHARED
    with _SHARED_LOCK:
        if _SHARED is None:
            _SHARED = PagePool()
        return _SHARED


# ---------------------------------------------------------------------------
# Model contract
# ---------------------------------------------------------------------------
class DecodeModel:
    """What a model must provide to serve through
    :class:`GenerativeEngine`.  Attributes: ``vocab``, ``n_layers``,
    ``n_heads``, ``head_dim``, ``max_seq``.  Two PURE functions of jax
    arrays (the engine owns paging, masking of dead rows, and batching
    — the model never sees a page table):

    - ``prefill(params, tokens, length) -> (logits, k, v)`` — one
      sequence, ``tokens`` ``(B,)`` int32 padded to a bucket,
      ``length`` the true prompt length; returns next-token ``logits``
      ``(vocab,)`` at position ``length-1`` plus the per-position cache
      ``k``/``v`` ``(L, B, H, D)`` (pad positions may hold garbage —
      the engine masks them out of every later attention).
    - ``decode(params, tokens, k_ctx, v_ctx, lengths) -> (logits,
      k_new, v_new)`` — one token per row, ``tokens`` ``(R,)`` int32 at
      positions ``lengths`` ``(R,)``, attending ``k_ctx``/``v_ctx``
      ``(L, R, C, H, D)`` where context position ``j`` is valid iff
      ``j < lengths[r]``; returns ``logits`` ``(R, vocab)`` and the new
      token's cache rows ``k_new``/``v_new`` ``(L, R, H, D)``.

    KV-cache exactness contract: ``decode`` over cached ``k``/``v``
    must equal a fresh ``prefill`` over the extended sequence (standard
    incremental attention) — that is what makes continuous-batched
    greedy decode token-exact vs the eager loop.
    """

    vocab: int
    n_layers: int
    n_heads: int
    head_dim: int
    max_seq: int

    def init_params(self, seed: int = 0):
        raise NotImplementedError

    def prefill(self, params, tokens, length):
        raise NotImplementedError

    def decode(self, params, tokens, k_ctx, v_ctx, lengths):
        raise NotImplementedError

    #: OPTIONAL third entry point enabling partial ("suffix") prefill
    #: for the content-addressed prefix cache — ``None`` means the
    #: engine recomputes the whole prompt on a partial hit (correct,
    #: just no savings).  Signature ``prefill_chunk(params, tokens,
    #: k_ctx, v_ctx, offset, length) -> (logits, k, v)``: ``tokens``
    #: ``(B,)`` int32 is the uncached suffix padded to a bucket, at
    #: global positions ``offset .. offset+B-1``; ``k_ctx``/``v_ctx``
    #: ``(L, C, H, D)`` is the paged cache where context position ``j``
    #: is valid iff ``j < offset``; ``length`` is the FULL sequence
    #: length.  Returns next-token ``logits`` ``(vocab,)`` at position
    #: ``length - 1`` plus the suffix cache ``k``/``v`` ``(L, B, H,
    #: D)``.  Exactness contract: identical to the same positions of a
    #: full ``prefill`` over the whole sequence (incremental attention
    #: again — that is what makes a cache hit token-exact).
    prefill_chunk = None

    #: OPTIONAL fourth entry point enabling speculative decoding
    #: (``MXNET_SPEC_DECODE``) — the batched multi-token scorer the
    #: verify program is built on.  Signature ``decode_chunk(params,
    #: tokens, k_ctx, v_ctx, lengths) -> (logits, k_new, v_new)``:
    #: ``tokens`` ``(R, S)`` int32, row ``r``'s chunk sitting at global
    #: positions ``lengths[r] .. lengths[r]+S-1``; ``k_ctx``/``v_ctx``
    #: ``(L, R, C, H, D)`` paged context where position ``j`` is valid
    #: iff ``j < lengths[r]``; in-chunk attention is causal.  Returns
    #: ``logits`` ``(R, S, vocab)`` (``logits[r, i]`` scores the token
    #: AFTER chunk position ``i``) and the chunk cache ``k_new``/
    #: ``v_new`` ``(L, R, S, H, D)``.  Exactness contract: position for
    #: position identical to ``S`` successive ``decode`` calls — that
    #: is what makes greedy speculative decode token-exact.
    decode_chunk = None


class TinyCausalLM(DecodeModel):
    """Reference :class:`DecodeModel`: a small pre-LN-free causal
    transformer (learned token + position embeddings, multi-head
    attention, ReLU MLP, untied output head) used by the parity tests
    and the dispatch-budget gate.  Everything is plain ``jnp`` on
    explicit parameter pytrees, so both entry points trace into single
    fused programs."""

    def __init__(self, vocab: int = 64, d_model: int = 32,
                 n_layers: int = 2, n_heads: int = 2,
                 d_mlp: Optional[int] = None, max_seq: int = 128):
        if d_model % n_heads:
            raise ValueError("d_model must divide by n_heads")
        self.vocab = vocab
        self.d_model = d_model
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.head_dim = d_model // n_heads
        self.d_mlp = d_mlp or 2 * d_model
        self.max_seq = max_seq

    def init_params(self, seed: int = 0):
        rng = onp.random.RandomState(seed)

        def mat(*shape, scale=None):
            scale = scale or 1.0 / math.sqrt(shape[0])
            return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)

        params = {
            "emb": mat(self.vocab, self.d_model, scale=0.5),
            "pos": mat(self.max_seq, self.d_model, scale=0.1),
            "out": mat(self.d_model, self.vocab),
            "layers": [],
        }
        for _ in range(self.n_layers):
            params["layers"].append({
                "wq": mat(self.d_model, self.d_model),
                "wk": mat(self.d_model, self.d_model),
                "wv": mat(self.d_model, self.d_model),
                "wo": mat(self.d_model, self.d_model),
                "w1": mat(self.d_model, self.d_mlp),
                "w2": mat(self.d_mlp, self.d_model),
            })
        return params

    # -- helpers -----------------------------------------------------------
    def _heads(self, x):
        return x.reshape(x.shape[:-1] + (self.n_heads, self.head_dim))

    def _attend(self, q, k, v, valid):
        # q (..., H, D); k/v (..., J, H, D); valid (..., J) bool
        scores = jnp.einsum("...hd,...jhd->...hj", q, k) \
            / math.sqrt(self.head_dim)
        scores = jnp.where(valid[..., None, :], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("...hj,...jhd->...hd", w, v)

    # -- contract ----------------------------------------------------------
    def prefill(self, params, tokens, length):
        b = tokens.shape[0]
        h = params["emb"][tokens] + params["pos"][:b]        # (B, d)
        pos = jnp.arange(b)
        causal = pos[:, None] >= pos[None, :]                # (B, B)
        ks, vs = [], []
        for lp in params["layers"]:
            q = self._heads(h @ lp["wq"])                    # (B, H, D)
            k = self._heads(h @ lp["wk"])
            v = self._heads(h @ lp["wv"])
            ks.append(k)
            vs.append(v)
            scores = jnp.einsum("ihd,jhd->ihj", q, k) \
                / math.sqrt(self.head_dim)
            scores = jnp.where(causal[:, None, :], scores, -1e30)
            w = jax.nn.softmax(scores, axis=-1)
            att = jnp.einsum("ihj,jhd->ihd", w, v)           # (B, H, D)
            h = h + att.reshape(b, self.d_model) @ lp["wo"]
            h = h + jax.nn.relu(h @ lp["w1"]) @ lp["w2"]
        logits = h[length - 1] @ params["out"]               # (vocab,)
        return logits, jnp.stack(ks), jnp.stack(vs)          # (L,B,H,D)

    def decode(self, params, tokens, k_ctx, v_ctx, lengths):
        r = tokens.shape[0]
        c = k_ctx.shape[2]
        h = params["emb"][tokens] + params["pos"][lengths]   # (R, d)
        ctx_valid = jnp.arange(c)[None, :] < lengths[:, None]  # (R, C)
        # the new token always attends itself (appended key slot C)
        valid = jnp.concatenate(
            [ctx_valid, jnp.ones((r, 1), bool)], axis=1)
        k_news, v_news = [], []
        for li, lp in enumerate(params["layers"]):
            q = self._heads(h @ lp["wq"])                    # (R, H, D)
            k_new = self._heads(h @ lp["wk"])
            v_new = self._heads(h @ lp["wv"])
            k_news.append(k_new)
            v_news.append(v_new)
            k = jnp.concatenate([k_ctx[li], k_new[:, None]], axis=1)
            v = jnp.concatenate([v_ctx[li], v_new[:, None]], axis=1)
            att = self._attend(q, k, v, valid)               # (R, H, D)
            h = h + att.reshape(r, self.d_model) @ lp["wo"]
            h = h + jax.nn.relu(h @ lp["w1"]) @ lp["w2"]
        logits = h @ params["out"]                           # (R, vocab)
        return logits, jnp.stack(k_news), jnp.stack(v_news)

    def prefill_chunk(self, params, tokens, k_ctx, v_ctx, offset,
                      length):
        b = tokens.shape[0]
        c = k_ctx.shape[1]
        pos = offset + jnp.arange(b)
        h = params["emb"][tokens] \
            + params["pos"][jnp.minimum(pos, self.max_seq - 1)]
        # cached context: every suffix token attends positions < offset
        ctx_valid = jnp.broadcast_to(
            jnp.arange(c)[None, :] < offset, (b, c))
        # in-chunk: causal, and pad keys (global pos >= length) masked
        ii = jnp.arange(b)
        chunk_valid = (ii[None, :] <= ii[:, None]) \
            & (ii[None, :] < length - offset)
        valid = jnp.concatenate([ctx_valid, chunk_valid], axis=1)
        ks, vs = [], []
        for li, lp in enumerate(params["layers"]):
            q = self._heads(h @ lp["wq"])                    # (B, H, D)
            k_new = self._heads(h @ lp["wk"])
            v_new = self._heads(h @ lp["wv"])
            ks.append(k_new)
            vs.append(v_new)
            k = jnp.concatenate([k_ctx[li], k_new], axis=0)  # (C+B,H,D)
            v = jnp.concatenate([v_ctx[li], v_new], axis=0)
            scores = jnp.einsum("ihd,jhd->ihj", q, k) \
                / math.sqrt(self.head_dim)
            scores = jnp.where(valid[:, None, :], scores, -1e30)
            w = jax.nn.softmax(scores, axis=-1)
            att = jnp.einsum("ihj,jhd->ihd", w, v)           # (B, H, D)
            h = h + att.reshape(b, self.d_model) @ lp["wo"]
            h = h + jax.nn.relu(h @ lp["w1"]) @ lp["w2"]
        logits = h[length - offset - 1] @ params["out"]      # (vocab,)
        return logits, jnp.stack(ks), jnp.stack(vs)          # (L,B,H,D)

    def decode_chunk(self, params, tokens, k_ctx, v_ctx, lengths):
        r, s = tokens.shape
        c = k_ctx.shape[2]
        pos = lengths[:, None] + jnp.arange(s)[None, :]      # (R, S)
        h = params["emb"][tokens] \
            + params["pos"][jnp.minimum(pos, self.max_seq - 1)]
        # cached context: chunk tokens attend positions < lengths
        ctx_valid = jnp.broadcast_to(
            jnp.arange(c)[None, None, :] < lengths[:, None, None],
            (r, s, c))
        # in-chunk: plain causal (every chunk position is a real token
        # — the engine masks rejected tails at the KV SCATTER, not here)
        ii = jnp.arange(s)
        chunk_valid = jnp.broadcast_to(
            (ii[None, :] <= ii[:, None])[None], (r, s, s))
        valid = jnp.concatenate([ctx_valid, chunk_valid], axis=2)
        k_news, v_news = [], []
        for li, lp in enumerate(params["layers"]):
            q = self._heads(h @ lp["wq"])                    # (R,S,H,D)
            k_new = self._heads(h @ lp["wk"])
            v_new = self._heads(h @ lp["wv"])
            k_news.append(k_new)
            v_news.append(v_new)
            k = jnp.concatenate([k_ctx[li], k_new], axis=1)  # (R,C+S,..)
            v = jnp.concatenate([v_ctx[li], v_new], axis=1)
            scores = jnp.einsum("rshd,rjhd->rshj", q, k) \
                / math.sqrt(self.head_dim)
            scores = jnp.where(valid[:, :, None, :], scores, -1e30)
            w = jax.nn.softmax(scores, axis=-1)
            att = jnp.einsum("rshj,rjhd->rshd", w, v)        # (R,S,H,D)
            h = h + att.reshape(r, s, self.d_model) @ lp["wo"]
            h = h + jax.nn.relu(h @ lp["w1"]) @ lp["w2"]
        logits = h @ params["out"]                           # (R,S,V)
        return logits, jnp.stack(k_news), jnp.stack(v_news)  # (L,R,S,..)


def high_agreement_pair(vocab: int = 64, d_model: int = 32,
                        target_layers: int = 4, draft_layers: int = 1,
                        n_heads: int = 2, max_seq: int = 128,
                        seed: int = 0):
    """A (target, target_params, draft, draft_params) fixture whose
    draft AGREES with the target exactly: both share embeddings, the
    position table, the output head, and the leading ``draft_layers``
    transformer layers, and the target's extra layers have ``wo = 0``
    and ``w2 = 0`` — each reduces to the identity (``h + att@0`` then
    ``h + relu(h@w1)@0``), so target logits == draft logits while the
    target still pays ``target_layers / draft_layers`` x the compute.
    Acceptance is 1.0 by construction — the fixture behind the
    dispatch-budget spec lane, the ``--speculative`` bench, and the
    speedup gate's high-agreement leg."""
    draft = TinyCausalLM(vocab, d_model, draft_layers, n_heads,
                         max_seq=max_seq)
    target = TinyCausalLM(vocab, d_model, target_layers, n_heads,
                          max_seq=max_seq)
    dp = draft.init_params(seed)
    tp = target.init_params(seed + 1)
    tp["emb"], tp["pos"], tp["out"] = dp["emb"], dp["pos"], dp["out"]
    for i in range(draft_layers):
        tp["layers"][i] = dp["layers"][i]
    for i in range(draft_layers, target_layers):
        tp["layers"][i]["wo"] = jnp.zeros_like(tp["layers"][i]["wo"])
        tp["layers"][i]["w2"] = jnp.zeros_like(tp["layers"][i]["w2"])
    return target, tp, draft, dp


# ---------------------------------------------------------------------------
# In-program stochastic sampling (temperature / top-k / top-p)
# ---------------------------------------------------------------------------
class SamplingSpec:
    """Per-request stochastic decoding spec.  ``temperature == 0`` IS
    greedy — the compiled sampler's 0-branch is bit-identical to the
    plain argmax, so a greedy request through a sampling-capable
    program decodes exactly as before.  ``top_k <= 0`` / ``top_p >= 1``
    disable their filters.  ``seed`` keys a counter-based PRNG: the
    token at absolute sequence position ``i`` always draws from
    ``fold_in(PRNGKey(seed), i)``, so a preemption re-prefill, a
    router failover, or a hedged duplicate replays the SAME tokens —
    determinism is positional, not iteration-order-dependent."""

    __slots__ = ("temperature", "top_k", "top_p", "seed")

    def __init__(self, temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0):
        # graftlint: disable=host-sync -- construction-time coercion of
        # the caller's HOST python scalars, no device value in sight
        t = float(temperature)
        if not (0.0 <= t < float("inf")):
            raise ValueError(f"temperature must be finite >= 0, got {t}")
        # graftlint: disable=host-sync -- same host-scalar coercion
        p = float(top_p)
        if not (0.0 < p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {p}")
        self.temperature = t
        self.top_k = int(top_k)
        self.top_p = p
        # PRNGKey folds the seed into uint32 space; coerce here so the
        # eager oracle, the compiled program, and the wire round-trip
        # all key from the identical value
        self.seed = int(seed) & 0x7FFFFFFF

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0

    def to_wire(self) -> Dict[str, Any]:
        """JSON-safe dict for serving_remote's frame protocol."""
        return {"temperature": self.temperature, "top_k": self.top_k,
                "top_p": self.top_p, "seed": self.seed}

    @classmethod
    def from_wire(cls, d: Dict[str, Any]) -> "SamplingSpec":
        return cls(temperature=d.get("temperature", 0.0),
                   top_k=d.get("top_k", 0), top_p=d.get("top_p", 1.0),
                   seed=d.get("seed", 0))

    def __eq__(self, other) -> bool:
        return (isinstance(other, SamplingSpec)
                and self.to_wire() == other.to_wire())

    def __repr__(self) -> str:
        return (f"SamplingSpec(temperature={self.temperature}, "
                f"top_k={self.top_k}, top_p={self.top_p}, "
                f"seed={self.seed})")


#: the no-arg spec every greedy request decodes under: all-zero traced
#: sampling operands, so greedy rows through the sampling-capable
#: programs hit the temperature-0 (bit-exact argmax) branch
GREEDY = SamplingSpec()


def token_key(seed, position):
    """Counter-based PRNG key for the token at absolute sequence
    ``position``: ``fold_in(PRNGKey(seed), position)``.  Pure function
    of (seed, position) — the whole replay-determinism story."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), position)


def _keep_mask(scaled, top_k, top_p):
    """Boolean keep-mask of the top-k AND nucleus (top-p) filters over
    temperature-scaled logits ``scaled`` (V,).  ``top_k <= 0`` /
    ``top_p >= 1`` pass everything; the rank-0 token is always kept."""
    v = scaled.shape[-1]
    order = jnp.argsort(-scaled)
    ranks = jnp.zeros((v,), jnp.int32).at[order].set(
        jnp.arange(v, dtype=jnp.int32))
    k_eff = jnp.where(top_k <= 0, jnp.int32(v),
                      jnp.asarray(top_k, jnp.int32))
    keep_k = ranks < k_eff
    # nucleus: smallest prefix of the sorted distribution whose mass
    # reaches top_p — exclusive cumsum < p keeps the boundary token
    sprobs = jax.nn.softmax(scaled[order])
    excl = jnp.cumsum(sprobs) - sprobs
    keep_p = (excl < top_p)[ranks]
    return keep_k & keep_p


def sample_token(logits, temperature, top_k, top_p, key):
    """Sample ONE token id from ``logits`` (V,) under temperature /
    top-k / top-p, via Gumbel-argmax on the masked scaled logits.
    ``temperature == 0`` returns the plain argmax BIT-IDENTICALLY (the
    sampled lane still traces, but the 0-branch selects the untouched
    argmax).  Traceable — this exact function runs inside the compiled
    decode/prefill programs AND in the eager oracle, which is what
    makes compiled-vs-eager parity seed-for-seed."""
    greedy = jnp.argmax(logits).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    keep = _keep_mask(scaled, top_k, top_p)
    masked = jnp.where(keep, scaled, -jnp.inf)
    sampled = jnp.argmax(
        masked + jax.random.gumbel(key, logits.shape)).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def _sample_dist(logits, temperature, top_k, top_p):
    """The full masked/normalized sampling distribution (V,) the
    request decodes under — one-hot argmax at ``temperature == 0``.
    This is the ``p``/``q`` both sides of speculative rejection
    sampling score, so acceptance is measured against EXACTLY the
    distribution :func:`sample_token` draws from."""
    v = logits.shape[-1]
    one_hot = jax.nn.one_hot(jnp.argmax(logits), v, dtype=logits.dtype)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    keep = _keep_mask(scaled, top_k, top_p)
    probs = jax.nn.softmax(jnp.where(keep, scaled, -jnp.inf))
    return jnp.where(temperature > 0.0, probs, one_hot)


def _sampling_args(sampling: Optional[SamplingSpec]):
    """The four host-side scalar operands a sampling spec rides the
    program signature as (traced, so heterogeneous configs share one
    program)."""
    s = sampling or GREEDY
    return (onp.float32(s.temperature), onp.int32(s.top_k),
            onp.float32(s.top_p), onp.int32(s.seed))


def eager_generate(model: DecodeModel, params, prompt: Sequence[int],
                   max_new_tokens: int, eos: Optional[int] = None,
                   sampling: Optional[SamplingSpec] = None
                   ) -> List[int]:
    """The one-request-at-a-time reference loop: a FULL forward over
    the tokens so far for every generated token (no KV cache, no
    batching, exact shapes) — the parity oracle for the continuous
    batcher.  ``sampling`` runs the SAME :func:`sample_token` the
    compiled programs trace, keyed by
    ``fold_in(PRNGKey(seed), position)`` — the seed-for-seed oracle
    for stochastic decode (``None`` / temperature 0 = greedy, the
    plain argmax, exactly as before)."""
    toks = [int(t) for t in prompt]
    out: List[int] = []
    temp, top_k, top_p, seed = _sampling_args(sampling)
    for _ in range(max_new_tokens):
        logits, _k, _v = model.prefill(
            params, jnp.asarray(toks, jnp.int32), len(toks))
        if sampling is None or sampling.greedy:
            nxt = int(jnp.argmax(logits))
        else:
            # the token being generated sits at absolute position
            # len(toks) — the same counter the engine's prefill
            # (position = prompt length) and decode (position =
            # cached + 1) programs fold in
            nxt = int(sample_token(logits, temp, top_k, top_p,
                                   token_key(seed, len(toks))))
        out.append(nxt)
        toks.append(nxt)
        if eos is not None and nxt == eos:
            break
    return out


# ---------------------------------------------------------------------------
# Requests + per-row state
# ---------------------------------------------------------------------------
class _GenRequest:
    __slots__ = ("prompt", "max_new", "eos", "out", "event", "error",
                 "t_enqueue", "t_done", "preempts", "joined", "trace_id",
                 "sampling")

    def __init__(self, prompt: List[int], max_new: int,
                 eos: Optional[int],
                 sampling: Optional[SamplingSpec] = None):
        self.prompt = prompt
        self.max_new = max_new
        self.eos = eos
        # per-request sampling spec (None = greedy).  Carried on the
        # request like t_enqueue: a preemption re-queue or a router
        # failover replays the SAME seed, and the position-keyed PRNG
        # makes the regenerated tokens identical
        self.sampling = sampling
        self.out: List[int] = []        # survives preemption
        self.event = threading.Event()
        self.error: Optional[BaseException] = None
        # ISSUE-15 request identity: minted (or inherited from the
        # router) at generate() entry and NEVER re-minted — a
        # preemption re-queue keeps one trace_id across its re-prefill,
        # exactly like the enqueue clock below
        self.trace_id: Optional[str] = None
        # the request's ONE enqueue clock: stamped here and NEVER reset
        # — a preemption re-queue keeps drawing its queue-wait/latency
        # from the original arrival, so p50/p99 stay honest
        self.t_enqueue = time.monotonic()
        self.t_done = 0.0
        self.preempts = 0
        # admission-order stamp (youngest-first preemption victims):
        # assigned at the FIRST prefill and kept across preemption
        # re-queues — without it a preempted sequence re-joined as the
        # "youngest" and was the next victim again (starvation under
        # sustained pool pressure)
        self.joined: Optional[int] = None


class _Row:
    __slots__ = ("req", "pages", "cached", "pending", "joined",
                 "draft_pages", "draft_cached")

    def __init__(self, req: _GenRequest, pages: List[int], cached: int,
                 pending: int, joined: int):
        self.req = req
        self.pages = pages        # page ids, in sequence order
        self.cached = cached      # tokens whose KV is in the pool
        self.pending = pending    # next token to feed the decode step
        self.joined = joined      # admission order, for youngest-first
                                  # preemption
        # speculative-decoding draft state: the draft model's OWN page
        # table in the shared pool (separate geometry, never published
        # to the prefix cache) and how many leading tokens hold VALID
        # draft KV.  A rejected speculation just rewinds draft_cached —
        # stale KV past it is masked out of every later attention, so
        # there is no rollback pass
        self.draft_pages: List[int] = []
        self.draft_cached = 0


class GenerativeEngine:
    """Continuous-batching greedy decoder over one :class:`DecodeModel`.

    ``eng = GenerativeEngine(model); toks = eng.generate([1,2,3],
    max_new_tokens=16)`` — ``generate`` is thread-safe and blocking;
    concurrent callers share decode iterations (one dispatch per
    token-batch).  Admission sheds loudly (:class:`faults.ShedError`)
    instead of queueing toward a timeout; see the module docstring for
    the scheduler/pool/SLO design.
    """

    def __init__(self, model: DecodeModel, params=None,
                 pool: Optional[PagePool] = None,
                 name: Optional[str] = None,
                 max_rows: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 slo_us: Optional[int] = None,
                 policy: Optional[BucketPolicy] = None,
                 eos: Optional[int] = None,
                 draft: Optional[DecodeModel] = None,
                 draft_params=None,
                 spec_k: Optional[Any] = None):
        self._model = model
        # weights, pool and every program live on ONE device — the
        # default context's — placed explicitly: host-committed params
        # (a numpy-loaded checkpoint) are moved here instead of pulling
        # the decode programs onto the host
        self._device = current_context().jax_device
        self._params = jax.device_put(
            params if params is not None else model.init_params(),
            self._device)
        self._pool = pool if pool is not None else shared_pool()
        self.name = name or type(model).__name__
        self._rows = int(max_rows if max_rows is not None
                         else _config.get("MXNET_SERVE_DECODE_ROWS"))
        self._max_queue = int(max_queue if max_queue is not None
                              else _config.get("MXNET_SERVE_MAX_QUEUE"))
        self._slo = (slo_us if slo_us is not None
                     else _config.get("MXNET_SERVE_SLO_US")) / 1e6
        # dispatch-gate urgency: tighter SLO dispatches first; engines
        # without one queue FIFO behind every SLO-bearing neighbor
        self._priority = self._slo if self._slo > 0 else float("inf")
        self._policy = policy or BucketPolicy()
        self._eos = eos
        self._geom = self._pool.register(
            model.n_layers, model.n_heads, model.head_dim)
        self._max_pages = -(-int(model.max_seq) // self._pool.page)
        self._programs = _pstore.scope("serving_decode")
        # -- speculative decoding (MXNET_SPEC_DECODE, ISSUE 19) --------
        # a co-hosted DRAFT model proposes k tokens per round and the
        # target scores all k+1 in ONE verify dispatch.  Draft KV pages
        # in the SAME pool (its own geometry; page ids stay distinct
        # because accounting is global) and is never published to the
        # prefix cache.  Requires the target to implement decode_chunk.
        self._draft = draft
        self._draft_params = None
        if draft is not None:
            if model.decode_chunk is None:
                raise ValueError(
                    "speculative decoding needs the TARGET model to "
                    "implement decode_chunk (the k+1-position verify "
                    "scorer)")
            if int(draft.vocab) != int(model.vocab):
                raise ValueError(
                    f"draft vocab {draft.vocab} != target vocab "
                    f"{model.vocab}: rejection sampling needs one "
                    "token space")
            self._draft_params = jax.device_put(
                draft_params if draft_params is not None
                else draft.init_params(), self._device)
            self._draft_geom = self._pool.register(
                draft.n_layers, draft.n_heads, draft.head_dim)
            self._draft_max_pages = -(-int(draft.max_seq)
                                      // self._pool.page)
        self._spec_programs = _pstore.scope("serving_spec")
        # ctor override wins over MXNET_SPEC_K (both accept 'auto')
        self._spec_k_setting = (str(spec_k) if spec_k is not None
                                else None)
        # sticky low-acceptance cutoff (the poisoned-draft degrade
        # path) + the acceptance-rate EMA that trips it
        self._spec_disabled = False
        self._spec_acc_ema: Optional[float] = None
        self._spec_rounds_done = 0
        # the cost table (admission prices a request from these EMAs —
        # never from a trial dispatch): measured seconds per prefill
        # bucket and per decode step
        self._cost: Dict[Any, float] = {}
        self._cv = threading.Condition()
        self._queue: "deque[_GenRequest]" = deque()
        self._live: List[_Row] = []
        self._joined = 0
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._draining = False    # per-replica drain (ISSUE 17)
        self._latencies: "deque[float]" = deque(maxlen=8192)
        # per-model counters live in the telemetry registry under a
        # unique instance prefix (family 'decode.engine'); stats() still
        # hands out plain ints via the Mapping view
        self._stats = _telemetry.CounterGroup(
            _telemetry.instance_name("decode.engine"),
            ("requests", "delivered", "tokens_out", "prefills",
             "decode_steps", "decode_row_util", "shed", "shed_queue",
             "shed_pool", "shed_slo", "shed_draining", "shed_deadline",
             "preempts", "slo_violations", "warmup_programs",
             "bucket_fallbacks", "spec_rounds", "spec_proposed",
             "spec_accepted", "spec_fallbacks"),
            doc=f"GenerativeEngine counters (model {self.name!r})",
            family="decode.engine")
        # the load() fields double as registered computed gauges
        # (ISSUE 17): the autoscaler and dashboards read the SAME
        # numbers the router balances on
        _telemetry.register_load_gauges(self, self._stats.prefix)
        from . import engine as _engine

        _engine.register_drainable(self)

    # -- public ------------------------------------------------------------
    def generate(self, prompt, max_new_tokens: int = 32,
                 eos: Optional[int] = None,
                 sampling: Optional[SamplingSpec] = None) -> List[int]:
        """Generate up to ``max_new_tokens`` token ids after ``prompt``
        (a 1-D int sequence/array); blocks until delivered.  ``sampling``
        (a :class:`SamplingSpec`) turns on temperature / top-k / top-p
        stochastic decode INSIDE the same compiled programs — the spec
        rides as traced per-row operands, so heterogeneous sampling
        configs share one program and join/retire never retraces;
        ``None`` (or temperature 0) is greedy, bit-identical to the
        pre-sampling argmax.  Raises :class:`faults.ShedError`
        IMMEDIATELY when admission refuses (queue/pool/SLO) — overload
        is loud, never a hang.

        Admission mints (or inherits, when routed) the ISSUE-15 request
        trace: admission/shed/preempt events, the prefill span, every
        decode iteration the request rides, and the lifecycle span all
        stamp one trace_id — kept across a preemption re-queue."""
        with _telemetry.trace_scope():
            return self._generate_traced(prompt, max_new_tokens, eos,
                                         sampling)

    def _generate_traced(self, prompt, max_new_tokens: int,
                         eos: Optional[int],
                         sampling: Optional[SamplingSpec] = None
                         ) -> List[int]:
        if self._closed:
            raise RuntimeError("GenerativeEngine is closed")
        # graftlint: disable=host-sync -- admission-time tokenization of
        # the caller's HOST prompt, before any device work exists
        toks = [int(t) for t in onp.asarray(prompt).ravel()]
        if not toks:
            raise ValueError("generate() needs a non-empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(toks) + max_new_tokens > self._model.max_seq:
            raise ValueError(
                f"prompt({len(toks)}) + max_new({max_new_tokens}) "
                f"exceeds model.max_seq={self._model.max_seq}")
        eos = eos if eos is not None else self._eos
        if sampling is not None and not isinstance(sampling,
                                                   SamplingSpec):
            raise TypeError(
                f"sampling must be a SamplingSpec, got {sampling!r}")
        req = _GenRequest(toks, int(max_new_tokens), eos,
                          sampling=sampling)
        req.trace_id = _telemetry.current_trace()
        self._stats.inc("requests")
        if req.trace_id is not None:
            _telemetry.event("admit", self.name, tokens=len(toks),
                             max_new=int(max_new_tokens))
        # the request's deadline budget (faults.deadline_scope on the
        # CALLER's thread — the router threads one per request): capture
        # the absolute expiry now so admission, queue wait, and decode
        # all draw from the one budget
        rem_us = _faults.deadline_remaining_us()
        until = (time.monotonic() + rem_us / 1e6
                 if rem_us is not None else None)
        self._admit(req)                 # may raise ShedError, fail-fast
        with self._cv:
            self._start_thread()
            self._queue.append(req)
            self._cv.notify_all()
        if until is None:
            delivered = req.event.wait(timeout=600.0)
        else:
            delivered = req.event.wait(
                timeout=max(0.0, until - time.monotonic()))
        if not delivered:
            if until is not None:
                # budget spent while queued/decoding: hand the request
                # back typed, NEVER a hang.  A still-queued request is
                # withdrawn outright; a live row finishes in the
                # background (its pages release at retirement) but this
                # caller's clock stops here.
                with self._cv:
                    try:
                        self._queue.remove(req)
                    except ValueError:
                        pass
                self._shed("deadline",
                           f"deadline budget exhausted after "
                           f"{(time.monotonic() - req.t_enqueue) * 1e6:.0f}"
                           "us (admission + queue + decode)")
            raise _faults.DeadlineExceeded(
                "generation not delivered within 600s (scheduler "
                "wedged?)")
        if req.error is not None:
            raise req.error
        self._latencies.append(req.t_done - req.t_enqueue)
        if self._slo > 0 and req.t_done - req.t_enqueue > self._slo:
            self._stats.inc("slo_violations")
        if req.trace_id is not None:
            _telemetry.event("retire", self.name,
                             tokens_out=len(req.out),
                             preempts=req.preempts)
        # request lifecycle span (admit -> prefill -> decode* -> retire)
        off = _telemetry.monotonic_offset_ns()     # to the spans' clock
        _telemetry.record_span(
            "decode.request", "serving",
            int(req.t_enqueue * 1e9) + off, int(req.t_done * 1e9) + off,
            args={"model": self.name, "tokens_out": len(req.out),
                  "preempts": req.preempts})
        return list(req.out)

    def spans(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Recent span records for this process's decode path (prefill
        dispatches + decode iterations, cat ``decode``) from the unified
        telemetry span buffer."""
        return _telemetry.spans(cat="decode", limit=limit)

    def load(self) -> Dict[str, float]:
        """Cheap live-load signals for a balancer (the PR-10 telemetry
        the replica router scores on): queue depth, live-row
        occupancy, and page-pool pressure.  No locks beyond the queue
        peek, no host syncs."""
        with self._cv:
            depth = len(self._queue)
            live = len(self._live)
        return {
            "queue_depth": depth + 0.0,          # host ints only: no
            "in_flight": live / max(self._rows, 1),  # device reads here
            "pool_pressure": 1.0 - (self._pool.free_pages()
                                    / max(self._pool.pages, 1)),
        }

    def stats(self) -> Dict[str, Any]:
        """Per-model counters + request-latency percentiles."""
        out = dict(self._stats)
        out["model"] = self.name
        out["programs"] = len(self._programs)
        out["spec_programs"] = len(self._spec_programs)
        out["spec_disabled"] = self._spec_disabled
        out["queue_depth"] = len(self._queue)
        out["live_rows"] = len(self._live)
        out["rows"] = self._rows
        out["pool"] = self._pool.stats()
        if out["decode_steps"]:
            out["rows_per_decode"] = (out["decode_row_util"]
                                      / out["decode_steps"])
        lat = sorted(self._latencies)
        if lat:
            out["p50_us"] = lat[len(lat) // 2] * 1e6
            out["p99_us"] = lat[min(len(lat) - 1,
                                    int(len(lat) * 0.99))] * 1e6
        else:
            out["p50_us"] = out["p99_us"] = 0.0
        return out

    def drain(self, timeout: float = 120.0) -> None:
        """engine.waitall() hook: block until every admitted request
        has been delivered (queue empty, no live rows)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._cv:
                if not self._queue and not self._live:
                    return
            time.sleep(0.002)

    # -- elastic-fleet hooks (ISSUE 17) --------------------------------------
    def begin_drain(self) -> None:
        """Per-replica drain (the router's ``drain_replica`` handback
        hook): flip this ONE engine draining — new admissions and the
        queued-but-not-live backlog shed typed ``draining``
        immediately (the router fails them over token-exact to a
        SERVING replica), while live rows keep decoding to
        completion.  The process-wide analog is the preemption
        notice; this is the same machinery scoped to one engine."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()

    def pool_audit(self) -> List[str]:
        """Detach-time page accounting (``PagePool.audit()``): every
        page free, cached, or referenced exactly once — [] == clean."""
        return list(self._pool.audit())

    def pool_in_use(self) -> int:
        """Referenced (non-free, non-cached) pages right now — the
        leak check a detaching replica must read 0 on."""
        return int(self._pool.in_use())

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- admission (site serving.admit) -------------------------------------
    def _estimate_s(self, req: _GenRequest) -> float:
        """Cost-table price of one request: its prefill bucket's EMA
        plus max_new decode-step EMAs.  Unknown entries price 0 — the
        table only ever makes admission MORE willing until it has
        measurements, never a trial dispatch."""
        b = self._policy.bucket(len(req.prompt))
        pre = self._cost.get(("prefill", b), 0.0)
        dec = self._cost.get("decode", 0.0)
        return pre + req.max_new * dec

    def _shed(self, kind: str, reason: str,
              cause: Optional[BaseException] = None):
        self._stats.inc("shed")
        self._stats.inc("shed_" + kind)
        _telemetry.event("shed", self.name, shed_kind=kind, reason=reason)
        _faults.record_event("serving.admit", "shed", cause,
                             model=self.name, kind=kind, reason=reason)
        err = ShedError(f"[{self.name}] {reason}", kind=kind)
        if cause is not None:
            raise err from cause
        raise err

    def _admit(self, req: _GenRequest) -> None:
        """Fail-fast admission in the CALLER's thread: the injectable
        ``serving.admit`` site plus the draining / queue / pool / SLO
        checks — every refusal is an immediate typed ShedError."""
        if _preemption.draining() or self._draining:
            # preemption notice taken (process-wide) or this ONE
            # replica is leaving the fleet (begin_drain, ISSUE 17):
            # NEVER park a new request toward the grace deadline —
            # shed typed so the client re-queues on another replica
            # or after the restart
            self._shed("draining",
                       "engine draining (preemption notice or replica "
                       "drain); re-queue this request on another "
                       "replica or after the restart")
        try:
            _faults.inject("serving.admit")
        except _faults.FaultInjected as e:
            self._shed("queue", "admission fault injected", cause=e)
        rem_us = _faults.deadline_remaining_us()
        if rem_us is not None:
            # the admission cost-table check draws from the request's
            # ONE deadline budget: a request that provably cannot
            # finish inside what is LEFT sheds now, paying zero compute
            est = self._estimate_s(req)
            if rem_us <= 0:
                self._shed("deadline",
                           "deadline budget already spent at admission")
            if est > rem_us / 1e6:
                self._shed("deadline",
                           f"cost table predicts {est * 1e6:.0f}us vs "
                           f"{rem_us}us remaining in the deadline "
                           "budget")
        with self._cv:
            qlen = len(self._queue)
        if qlen >= self._max_queue:
            self._shed("queue",
                       f"admission queue full ({qlen} >= "
                       f"MXNET_SERVE_MAX_QUEUE={self._max_queue})")
        need = -(-(len(req.prompt) + req.max_new) // self._pool.page)
        if need > self._pool.pages:
            self._shed("pool",
                       f"request needs {need} KV pages, pool holds "
                       f"{self._pool.pages} total — can never fit")
        if self._slo > 0:
            est = (qlen + 1) * self._estimate_s(req)
            if est > self._slo:
                self._shed("slo",
                           f"cost table predicts {est*1e6:.0f}us wait "
                           f"vs SLO {self._slo*1e6:.0f}us "
                           f"({qlen} queued ahead)")

    # -- scheduler ----------------------------------------------------------
    def _start_thread(self) -> None:
        if self._thread is None and not self._closed:
            self._thread = threading.Thread(
                target=self._sched_loop, daemon=True,
                name=f"mxnet-decode-{self.name}")
            self._thread.start()

    def _sched_loop(self) -> None:
        while True:
            with self._cv:
                while (not self._queue and not self._live
                       and not self._closed):
                    self._cv.wait(timeout=0.1)
                if self._closed and not self._queue and not self._live:
                    return
            try:
                self._iteration()
            except BaseException as e:      # deliver, never wedge
                self._fail_all(e)

    def _fail_all(self, e: BaseException) -> None:
        with self._cv:
            rows, self._live = self._live, []
            reqs = list(self._queue)
            self._queue.clear()
        for row in rows:
            self._release(row)
            row.req.error = e
            row.req.t_done = time.monotonic()
            row.req.event.set()
        for req in reqs:
            req.error = e
            req.t_done = time.monotonic()
            req.event.set()

    def _requeue_for_drain(self) -> None:
        """Drain handback (process preemption or a per-replica
        ``begin_drain``): queued-but-not-yet-prefilled requests are
        handed BACK to their callers as typed ``draining`` sheds (their
        pages were never allocated, their tokens never computed — a
        resubmission after restart, or a router failover to a SERVING
        replica, is token-exact by greedy determinism), while LIVE
        rows keep decoding to completion.  That bounds the drain to
        the in-flight tail and guarantees 0 leaked pages once
        ``engine.waitall()`` returns."""
        with self._cv:
            reqs, self._queue = list(self._queue), deque()
        for req in reqs:
            self._stats.inc("shed")
            self._stats.inc("shed_draining")
            with _telemetry.trace_scope(trace_id=req.trace_id):
                _telemetry.event(
                    "shed", self.name, shed_kind="draining",
                    reason="queued request re-queued at drain")
                _faults.record_event(
                    "serving.admit", "shed", model=self.name,
                    kind="draining",
                    reason="queued request re-queued at drain",
                    tokens_done=len(req.out))
            req.error = ShedError(
                f"[{self.name}] draining after a preemption notice "
                "before this request was scheduled; re-queue it after "
                "the restart (greedy decode regenerates its "
                f"{len(req.out)} partial token(s) token-exactly)",
                kind="draining")
            req.t_done = time.monotonic()
            req.event.set()

    def _iteration(self) -> None:
        """One scheduler iteration: admit prefills into free rows, run
        one decode step over the union of live sequences, retire."""
        if _preemption.draining() or self._draining:
            self._requeue_for_drain()
        # -- join: newly arrived prefills slot into freed rows
        while len(self._live) < self._rows:
            with self._cv:
                if not self._queue:
                    break
                req = self._queue.popleft()
            try:
                self._prefill(req)
                continue
            except PagePoolExhausted:
                with self._cv:
                    self._queue.appendleft(req)   # head-of-line: retry
                if not self._live:
                    # nothing of OURS will retire and free pages; wait
                    # briefly for other engines, then shed loudly
                    if self._wait_for_pages(req):
                        continue
                    with self._cv:
                        self._queue.remove(req)
                    self._stats.inc("shed")
                    self._stats.inc("shed_pool")
                    with _telemetry.trace_scope(trace_id=req.trace_id):
                        _telemetry.event(
                            "shed", self.name, shed_kind="pool",
                            reason="pool exhausted at prefill")
                        _faults.record_event(
                            "serving.admit", "shed", model=self.name,
                            kind="pool",
                            reason="pool exhausted at prefill")
                    req.error = ShedError(
                        f"[{self.name}] KV page pool exhausted at "
                        "prefill and no progress upstream")
                    req.t_done = time.monotonic()
                    req.event.set()
                break
            except BaseException as e:
                # a bad REQUEST (untraceable bucket, model error) fails
                # only its own caller — the engine and its neighbors
                # keep serving
                req.error = e
                req.t_done = time.monotonic()
                req.event.set()
        # -- decode: one dispatch for the union of live sequences —
        # or, when the cost table says speculation pays, one DRAFT
        # dispatch + one VERIFY dispatch for up to k+1 tokens per row
        if self._live:
            k = self._spec_should_engage()
            if not (k and self._spec_round(k)):
                self._decode_step()
            self._retire_finished()

    def _wait_for_pages(self, req: _GenRequest, budget: float = 5.0
                        ) -> bool:
        """Pool empty and this engine idle: another engine's retirement
        is the only path to pages.  Poll briefly; True = pages appeared."""
        need = -(-len(req.prompt) // self._pool.page) or 1
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            if self._pool.free_pages() >= need:
                return True
            if self._closed:
                return False
            time.sleep(0.005)
        return False

    # -- prefill ------------------------------------------------------------
    def _prefill(self, req: _GenRequest) -> None:
        """Compile-per-bucket prompt program: embeds the prompt, writes
        its KV into freshly allocated pages (scatter INSIDE the
        program), and emits the first generated token.  Runs on the
        scheduler thread — it re-enters the request's trace so the
        prefill span (incl. the re-prefill after a preemption
        re-queue) stamps the ONE trace_id minted at admission."""
        with _telemetry.trace_scope(trace_id=req.trace_id):
            self._prefill_traced(req)

    def _prefix_on(self) -> bool:
        return bool(_config.get("MXNET_PREFIX_CACHE"))

    def _prefix_min_blocks(self) -> int:
        """Cost-table floor for content addressing: only prompts
        spanning at least this many page-blocks are hashed, probed,
        and published.  Priced from measured EMAs — the per-block probe
        cost must undercut the per-block prefill compute a hit saves;
        unmeasured tables price the floor at 1, so caching starts on
        and the table only ever RAISES the bar."""
        probe = self._cost.get(("prefix", "probe"), 0.0)
        saved = self._cost.get(("prefix", "block"), 0.0)
        if probe <= 0.0 or saved <= 0.0:
            return 1
        return max(1, int(math.ceil(probe / saved)))

    def _prefix_lookup(self, prompt: List[int]
                       ) -> Tuple[List[bytes], List[int]]:
        """Hash the prompt's block chain and ACQUIRE the longest cached
        prefix.  Returns ``(keys, hit_pages)`` — both empty when the
        knob is off or the prompt is under the cost-table floor (the
        off path never hashes: zero overhead)."""
        if not self._prefix_on():
            return [], []
        t0 = time.perf_counter()
        keys = _chain_keys(prompt, self._pool.page, self._geom)
        if len(keys) < self._prefix_min_blocks():
            return [], []
        hits = self._pool.lookup(self._geom, keys)
        self._ema(("prefix", "probe"),
                  (time.perf_counter() - t0) / len(keys))
        return keys, hits

    def prefix_probe(self, prompt: Sequence[int]) -> int:
        """How many LEADING page-blocks of ``prompt``'s hash chain are
        resident in this engine's pool — the router's prefix-affinity
        signal.  No reference bump, no device work, 0 when the cache
        is off."""
        if not self._prefix_on():
            return 0
        toks = [int(t) for t in prompt]
        return self._pool.holds(
            self._geom, _chain_keys(toks, self._pool.page, self._geom))

    def _prefill_traced(self, req: _GenRequest) -> None:
        prompt = req.prompt + req.out     # re-grown after preemption
        n = len(prompt)
        page = self._pool.page
        keys, hits = self._prefix_lookup(prompt)
        blocks = len(keys)
        if hits and min(len(hits) * page, n) >= n:
            # FULL hit: every block (incl. the partial tail) resident —
            # ZERO prefill dispatch.  Rewind one position and let the
            # ordinary decode step recompute the last prompt token's
            # logits (the KV-exactness contract makes that token-exact
            # with a fresh prefill); the write position lands in a
            # shared page, so _ensure_page COW-forks before the step.
            _telemetry.event("prefix_hit", self.name,
                             hit_blocks=blocks, blocks=blocks,
                             hit_rate=1.0, tokens=n)
            if req.joined is None:
                req.joined = self._joined
                self._joined += 1
            row = _Row(req, hits, cached=n - 1, pending=prompt[-1],
                       joined=req.joined)
            if self._done(row):
                self._deliver(row)
            else:
                self._live.append(row)
            return
        if hits and self._model.prefill_chunk is None:
            # no partial-prefill entry point on this model: release the
            # hit references and recompute the whole prompt (correct,
            # just no savings)
            self._pool.free(hits)
            hits = []
        cached = len(hits) * page   # page-aligned: only the final
        m = n - cached              # block is ever partial, and a
                                    # partial-tail hit is a FULL hit
        bucket = self._policy.bucket(m)
        if bucket is None:                # above the largest bucket
            self._stats.inc("bucket_fallbacks")
            bucket = m
        # the position table only spans max_seq (generate() already
        # bounds n itself)
        bucket = min(bucket, int(self._model.max_seq))
        try:
            fresh = self._pool.alloc(-(-n // page) - len(hits))
        except BaseException:
            if hits:
                self._pool.free(hits)    # lookup references NEVER leak
            raise
        pages = hits + fresh
        try:
            tokens = onp.zeros((bucket,), onp.int32)
            tokens[:m] = prompt[cached:]
            table = onp.full((self._max_pages,), self._pool.trash,
                             onp.int32)
            table[:len(pages)] = pages
            span_args: Dict[str, Any] = {"model": self.name,
                                         "bucket": bucket, "tokens": n}
            if keys:
                span_args.update(
                    hit_blocks=len(hits), blocks=blocks,
                    hit_rate=len(hits) / max(blocks, 1))
            samp = _sampling_args(req.sampling)
            t0 = time.perf_counter()
            with _telemetry.span("decode.prefill", cat="decode",
                                 args=span_args):
                self._pool.gate.acquire(self._priority)
                try:
                    with self._pool.exclusive(self._geom):
                        k, v = self._pool.storage(self._geom)
                        if hits:
                            # suffix-only dispatch: the cached prefix
                            # rides in via the page-table gather
                            rec = self._chunk_program(bucket)
                            first, k, v = rec(self._params,
                                              jnp.asarray(tokens),
                                              jnp.int32(cached),
                                              jnp.int32(n),
                                              jnp.asarray(table),
                                              *samp, k, v)
                        else:
                            rec = self._prefill_program(bucket)
                            first, k, v = rec(self._params,
                                              jnp.asarray(tokens),
                                              jnp.int32(n),
                                              jnp.asarray(table),
                                              *samp, k, v)
                        first = int(first)    # host read = real cost
                        self._pool.set_storage(self._geom, k, v)
                finally:
                    self._pool.gate.release()
            secs = time.perf_counter() - t0
            self._ema(("prefill", bucket), secs)
            # per-block prefill price == what one cached block saves
            # (feeds the _prefix_min_blocks floor)
            self._ema(("prefix", "block"), secs * page / max(m, 1))
            self._stats.inc("prefills")
            if keys:
                self._pool.publish(
                    self._geom, [(keys[i], pages[i])
                                 for i in range(len(hits), blocks)])
        except BaseException:
            self._pool.free(pages)
            raise
        req.out.append(first)
        if req.joined is None:           # first admission only: a
            req.joined = self._joined    # preemption re-queue keeps its
            self._joined += 1            # original seniority
        row = _Row(req, pages, cached=n, pending=first,
                   joined=req.joined)
        if self._done(row):
            self._deliver(row)
        else:
            self._live.append(row)

    def _prefill_program(self, bucket: int):
        rec = self._programs.lookup(("prefill", bucket))
        if rec is not None:
            return rec
        return self._build_prefill(bucket)

    def _build_prefill(self, bucket: int):
        model, pool, page = self._model, self._pool, self._pool.page
        trash = pool.trash

        def prefill_fn(params, tokens, length, table, temp, top_k,
                       top_p, seed, k_pool, v_pool):
            _pstore.count_trace("serving_decode")
            logits, k, v = model.prefill(params, tokens, length)
            pos = jnp.arange(bucket)
            valid = pos < length
            pidx = jnp.where(valid, table[pos // page], trash)
            slot = pos % page
            # k/v (L, B, H, D) -> per-position rows (B, L, H, D)
            k_pool = k_pool.at[pidx, slot].set(k.transpose(1, 0, 2, 3))
            v_pool = v_pool.at[pidx, slot].set(v.transpose(1, 0, 2, 3))
            # the first generated token sits at absolute position
            # ``length`` — its counter-based key.  temperature 0 is
            # the bit-exact argmax branch (greedy unchanged)
            nxt = sample_token(logits, temp, top_k, top_p,
                               token_key(seed, length))
            return nxt, k_pool, v_pool

        jitted = jax.jit(prefill_fn, donate_argnums=self._donate)
        args = self._prefill_specs(bucket)
        rec = _pstore.build("serving_decode", jitted, args,
                            label=f"{self.name}[prefill b={bucket}]")
        self._programs.insert(("prefill", bucket), rec)
        return rec

    def _chunk_program(self, bucket: int):
        rec = self._programs.lookup(("prefill_chunk", bucket))
        if rec is not None:
            return rec
        return self._build_prefill_chunk(bucket)

    def _build_prefill_chunk(self, bucket: int):
        """Suffix ("chunk") prefill program, one per bucket of the
        SUFFIX length: gathers the cached prefix context through the
        page table (exactly the decode gather), runs the model's
        ``prefill_chunk``, and scatters only the suffix KV.  Compiled
        lazily on the first partial hit — warmup's program census and
        the dispatch-budget gate's cold-path counts stay untouched."""
        model, pool, page = self._model, self._pool, self._pool.page
        trash = pool.trash
        max_pages = self._max_pages

        def prefill_chunk_fn(params, tokens, offset, length, table,
                             temp, top_k, top_p, seed, k_pool, v_pool):
            _pstore.count_trace("serving_decode")
            # page-table gather: (P, page, L, H, D) -> (L, C, H, D)
            k_ctx = k_pool[table].reshape(
                max_pages * page, model.n_layers, model.n_heads,
                model.head_dim).transpose(1, 0, 2, 3)
            v_ctx = v_pool[table].reshape(
                max_pages * page, model.n_layers, model.n_heads,
                model.head_dim).transpose(1, 0, 2, 3)
            logits, k, v = model.prefill_chunk(
                params, tokens, k_ctx, v_ctx, offset, length)
            pos = offset + jnp.arange(bucket)
            valid = pos < length
            # bucket padding can point past the table — clamp, then
            # mask to the trash page
            pidx = jnp.where(
                valid, table[jnp.minimum(pos // page, max_pages - 1)],
                trash)
            slot = pos % page
            k_pool = k_pool.at[pidx, slot].set(k.transpose(1, 0, 2, 3))
            v_pool = v_pool.at[pidx, slot].set(v.transpose(1, 0, 2, 3))
            nxt = sample_token(logits, temp, top_k, top_p,
                               token_key(seed, length))
            return nxt, k_pool, v_pool

        jitted = jax.jit(prefill_chunk_fn,
                         donate_argnums=self._chunk_donate)
        rec = _pstore.build(
            "serving_decode", jitted, self._chunk_specs(bucket),
            label=f"{self.name}[prefill_chunk b={bucket}]")
        self._programs.insert(("prefill_chunk", bucket), rec)
        return rec

    # -- decode -------------------------------------------------------------
    def _decode_step(self) -> None:
        """ONE dispatch for every live sequence: gather pages, attend,
        sample, scatter the new KV — all inside the one compiled decode
        program.  Dead rows run masked into the trash page."""
        for row in list(self._live):
            # a preemption inside an earlier row's _ensure_page may have
            # evicted THIS row — allocating onto an evicted row would
            # orphan the page
            if row in self._live:
                self._ensure_page(row)
        if not self._live:
            return
        rec = self._decode_program()
        r = self._rows
        tokens = onp.zeros((r,), onp.int32)
        tables = onp.full((r, self._max_pages), self._pool.trash,
                          onp.int32)
        lengths = onp.zeros((r,), onp.int32)
        temps = onp.zeros((r,), onp.float32)
        top_ks = onp.zeros((r,), onp.int32)
        top_ps = onp.ones((r,), onp.float32)
        seeds = onp.zeros((r,), onp.int32)
        for i, row in enumerate(self._live):
            tokens[i] = row.pending
            tables[i, :len(row.pages)] = row.pages
            lengths[i] = row.cached
            (temps[i], top_ks[i], top_ps[i],
             seeds[i]) = _sampling_args(row.req.sampling)
        t0 = time.perf_counter()
        step_args: Dict[str, Any] = {"model": self.name,
                                     "rows": len(self._live)}
        traces = [row.req.trace_id for row in self._live
                  if row.req.trace_id is not None]
        if traces:
            # one decode dispatch serves MANY live requests: the span
            # lists every rider's trace so telemetry.trace(id) returns
            # each request's decode iterations
            step_args["trace_ids"] = traces
        with _telemetry.span("decode.step", cat="decode",
                             args=step_args):
            self._pool.gate.acquire(self._priority)
            try:
                with self._pool.exclusive(self._geom):
                    k, v = self._pool.storage(self._geom)
                    nxt, k, v = rec(self._params, jnp.asarray(tokens),
                                    jnp.asarray(tables),
                                    jnp.asarray(lengths),
                                    jnp.asarray(temps),
                                    jnp.asarray(top_ks),
                                    jnp.asarray(top_ps),
                                    jnp.asarray(seeds), k, v)
                    # graftlint: disable=host-sync -- THE one deliberate
                    # host read per decode iteration (next-token ids feed
                    # the host scheduler); the dispatch-budget gate counts it
                    nxt = onp.asarray(nxt)
                    self._pool.set_storage(self._geom, k, v)
            finally:
                self._pool.gate.release()
        self._ema("decode", time.perf_counter() - t0)
        self._stats.inc("decode_steps")
        self._stats.inc("decode_row_util", len(self._live))
        for i, row in enumerate(self._live):
            row.cached += 1               # pending's KV is now paged
            row.pending = int(nxt[i])
            row.req.out.append(row.pending)
        self._stats.inc("tokens_out", len(self._live))

    def _ensure_page(self, row: _Row) -> None:
        """The incoming token writes KV at position ``row.cached`` —
        allocate its page if that position opens a new one, and
        copy-on-write-fork it first when it is shared or published
        (content-addressed pages are immutable; the fork point IS the
        divergence point between requests sharing a prefix).
        Exhaustion preempts the YOUNGEST other live sequence
        (vLLM-style recompute preemption: pages freed, request
        re-queued at the head; greedy decode makes the recomputed
        continuation token-exact)."""
        if row.cached < len(row.pages) * self._pool.page:
            i = row.cached // self._pool.page
            if not self._pool.shared(row.pages[i]):
                return

            def grow() -> None:
                row.pages[i] = self._pool.fork(self._geom,
                                               row.pages[i])
        else:

            def grow() -> None:
                row.pages.extend(self._pool.alloc(1))
        while True:
            try:
                grow()
                return
            except PagePoolExhausted as e:
                victims = [x for x in self._live if x is not row]
                if not victims:
                    # this sequence alone outgrew the pool: loud typed
                    # failure, never a silent truncation
                    self._live.remove(row)
                    self._release(row)
                    self._stats.inc("shed")
                    self._stats.inc("shed_pool")
                    with _telemetry.trace_scope(
                            trace_id=row.req.trace_id):
                        _telemetry.event(
                            "shed", self.name, shed_kind="pool",
                            reason="single sequence outgrew pool")
                        _faults.record_event(
                            "serving.admit", "shed", e, model=self.name,
                            kind="pool",
                            reason="single sequence outgrew pool")
                    row.req.error = ShedError(
                        f"[{self.name}] sequence needs page "
                        f"{len(row.pages) + 1}, pool exhausted with no "
                        "other sequence to preempt")
                    row.req.t_done = time.monotonic()
                    row.req.event.set()
                    return
                self._preempt(max(victims, key=lambda x: x.joined))

    def _preempt(self, row: _Row) -> None:
        self._live.remove(row)
        self._release(row)
        row.req.preempts += 1
        self._stats.inc("preempts")
        # the preempt event belongs to the EVICTED request's trace, not
        # whichever row's page allocation triggered the eviction
        with _telemetry.trace_scope(trace_id=row.req.trace_id):
            _telemetry.event("preempt", self.name,
                             tokens_done=len(row.req.out))
            _faults.record_event("serving.admit", "preempt",
                                 model=self.name,
                                 tokens_done=len(row.req.out))
        with self._cv:
            self._queue.appendleft(row.req)

    def _decode_program(self):
        rec = self._programs.lookup(("decode",))
        if rec is not None:
            return rec
        return self._build_decode()

    def _build_decode(self):
        model, page = self._model, self._pool.page

        def decode_fn(params, tokens, tables, lengths, temps, top_ks,
                      top_ps, seeds, k_pool, v_pool):
            _pstore.count_trace("serving_decode")
            # page-table gather: (R, P) -> (R, P, page, L, H, D)
            k_ctx = k_pool[tables]
            v_ctx = v_pool[tables]
            r, p = tables.shape[0], tables.shape[1]
            # -> (L, R, C=P*page, H, D)
            k_ctx = k_ctx.reshape(r, p * page, model.n_layers,
                                  model.n_heads, model.head_dim
                                  ).transpose(2, 0, 1, 3, 4)
            v_ctx = v_ctx.reshape(r, p * page, model.n_layers,
                                  model.n_heads, model.head_dim
                                  ).transpose(2, 0, 1, 3, 4)
            logits, k_new, v_new = model.decode(
                params, tokens, k_ctx, v_ctx, lengths)
            # scatter the new token's KV at (page of position len, slot)
            rows = jnp.arange(r)
            pidx = tables[rows, lengths // page]
            slot = lengths % page
            # (L, R, H, D) -> (R, L, H, D) rows
            k_pool = k_pool.at[pidx, slot].set(
                k_new.transpose(1, 0, 2, 3))
            v_pool = v_pool.at[pidx, slot].set(
                v_new.transpose(1, 0, 2, 3))
            # per-row counter-based keys: the token being sampled lands
            # at absolute position lengths+1 (pending occupies lengths).
            # Sampling params ride as TRACED arrays — heterogeneous
            # configs across rows never retrace
            keys = jax.vmap(
                lambda s, p: token_key(s, p))(seeds, lengths + 1)
            nxt = jax.vmap(sample_token)(logits, temps, top_ks,
                                         top_ps, keys)
            return nxt.astype(jnp.int32), k_pool, v_pool

        jitted = jax.jit(decode_fn, donate_argnums=self._donate)
        rec = _pstore.build("serving_decode", jitted,
                            self._decode_specs(),
                            label=f"{self.name}[decode r={self._rows}]")
        self._programs.insert(("decode",), rec)
        return rec

    # -- speculative decoding (MXNET_SPEC_DECODE, ISSUE 19) ------------------
    #: draft depth ceiling under MXNET_SPEC_K=auto: the draft-round
    #: program is built ONCE at this k and verify consumes the first k
    #: of its proposals, so auto-k never retraces the draft
    _SPEC_AUTO_KMAX = 4

    def _spec_setting(self) -> str:
        return (self._spec_k_setting
                if self._spec_k_setting is not None
                else str(_config.get("MXNET_SPEC_K")))

    def _spec_kmax(self) -> int:
        s = self._spec_setting()
        return self._SPEC_AUTO_KMAX if s == "auto" else max(1, int(s))

    def _spec_should_engage(self) -> int:
        """Per-round arbitration: returns the k to draft this round, or
        0 for a plain decode step.  Speculation engages only when the
        cost table says a round pays for itself —
        ``(E_acc + 1) * t_target > t_draft + t_verify`` — over MEASURED
        per-round EMAs (arXiv:2008.01040: priced, never guessed);
        unmeasured entries engage optimistically, so the table only
        ever turns speculation OFF once it has numbers."""
        if (self._draft is None
                or not _config.get("MXNET_SPEC_DECODE")
                or self._spec_disabled):
            return 0
        s = self._spec_setting()
        kmax = self._spec_kmax()
        k = self._spec_auto_k() if s == "auto" else kmax
        # every live row must fit the draft's kmax-deep proposal run
        # AND the k+1-position verify chunk inside max_seq
        for row in self._live:
            if row.cached + kmax + 1 > int(self._model.max_seq) - 1:
                self._spec_fallback()
                return 0
        t_t = self._cost.get("decode")
        t_d = self._cost.get(("spec", "draft"))
        t_v = self._cost.get(("spec_verify", k))
        if t_t is not None and t_d is not None and t_v is not None:
            # optimistic bootstrap: an unmeasured acceptance EMA prices
            # as k (a HOST int off the cost table, not a device read)
            # graftlint: disable=host-sync -- host-scalar coercion
            e_acc = self._cost.get(("spec", "acc"), float(k))
            if (e_acc + 1.0) * t_t <= t_d + t_v:
                self._spec_fallback()
                return 0
        return k

    def _spec_auto_k(self) -> int:
        """``MXNET_SPEC_K=auto``: pick the verify depth k maximizing
        expected tokens per second from the same EMAs the arbiter
        reads — ``E_tok(k) = (1 - beta^(k+1)) / (1 - beta)`` over the
        acceptance-rate EMA ``beta``, priced at
        ``t_draft + t_verify(k)``.  Unmeasured shapes are tried first
        (smallest k), so every candidate gets one measurement before
        the scores mean anything."""
        t_d = self._cost.get(("spec", "draft"))
        beta = self._spec_acc_ema
        if t_d is None or beta is None:
            return self._SPEC_AUTO_KMAX
        beta = min(max(beta, 0.0), 0.999)
        best_k, best = self._SPEC_AUTO_KMAX, -1.0
        for k in range(1, self._SPEC_AUTO_KMAX + 1):
            t_v = self._cost.get(("spec_verify", k))
            if t_v is None:
                return k
            e_tok = (1.0 - beta ** (k + 1)) / max(1.0 - beta, 1e-6)
            score = e_tok / max(t_d + t_v, 1e-12)
            if score > best:
                best, best_k = score, k
        return best_k

    def _spec_fallback(self) -> None:
        _SPEC_STATS.inc("fallback_rounds")
        self._stats.inc("spec_fallbacks")

    def _spec_autodisable(self, reason: str, **fields) -> None:
        """Sticky degrade to plain decode (the poisoned-draft path):
        once measured acceptance collapses or a draft dispatch fails,
        speculation stays off for this engine's lifetime — plain decode
        is always correct, so the failure mode costs throughput only."""
        if self._spec_disabled:
            return
        self._spec_disabled = True
        _SPEC_STATS.inc("autodisabled")
        _telemetry.event("spec.autodisabled", self.name,
                         reason=reason, **fields)
        _faults.record_event("serving.spec", "autodisabled",
                             model=self.name, reason=reason)

    def _ensure_spec_pages(self, row: _Row, last_pos: int) -> bool:
        """Grow (and COW-fork, when a leading page is shared or
        published) the TARGET page table to cover verify writes through
        ``last_pos`` — NON-preempting: speculation is opportunistic, so
        exhaustion just means "not this round" and plain decode
        proceeds under the ordinary preemption rules."""
        page = self._pool.page
        try:
            for i in range(row.cached // page, last_pos // page + 1):
                if i < len(row.pages):
                    if self._pool.shared(row.pages[i]):
                        row.pages[i] = self._pool.fork(self._geom,
                                                       row.pages[i])
                else:
                    row.pages.extend(self._pool.alloc(1))
            return True
        except PagePoolExhausted:
            return False

    def _ensure_draft_ready(self, row: _Row, kmax: int) -> bool:
        """Draft pages covering this round's writes (positions
        ``row.cached .. row.cached + kmax - 1``) plus a draft PREFILL
        when the draft lags the target by more than the in-round
        catch-up step can absorb (first spec round for the row, or
        plain-decoded rounds while speculation was disengaged).  Draft
        pages are never shared or published — no COW, and a rejected
        speculation just rewinds ``draft_cached`` (stale KV past it is
        masked out of every later attention: no rollback pass)."""
        page = self._pool.page
        c = row.cached
        try:
            while len(row.draft_pages) * page <= c + kmax - 1:
                row.draft_pages.extend(self._pool.alloc(1))
        except PagePoolExhausted:
            return False
        if c - row.draft_cached > 1 and c > 0:
            self._draft_prefill(row)
        return True

    def _draft_prefill(self, row: _Row) -> None:
        """One bucketed draft-prefill dispatch: writes the draft's KV
        for the row's committed prefix so the round program can start
        proposing from ``pending``."""
        c = row.cached
        seq = (row.req.prompt + row.req.out)[:c]
        bucket = self._policy.bucket(c)
        if bucket is None:
            bucket = c
        bucket = min(bucket, int(self._draft.max_seq))
        tokens = onp.zeros((bucket,), onp.int32)
        tokens[:c] = seq
        table = onp.full((self._draft_max_pages,), self._pool.trash,
                         onp.int32)
        table[:len(row.draft_pages)] = row.draft_pages
        rec = self._draft_prefill_program(bucket)
        t0 = time.perf_counter()
        with _telemetry.span("decode.spec_draft_prefill", cat="decode",
                             args={"model": self.name,
                                   "bucket": bucket, "tokens": c}):
            self._pool.gate.acquire(self._priority)
            try:
                with self._pool.exclusive(self._draft_geom):
                    dk, dv = self._pool.storage(self._draft_geom)
                    dk, dv = rec(self._draft_params,
                                 jnp.asarray(tokens), jnp.int32(c),
                                 jnp.asarray(table), dk, dv)
                    self._pool.set_storage(self._draft_geom, dk, dv)
            finally:
                self._pool.gate.release()
        self._ema(("spec", "draft_prefill"), time.perf_counter() - t0)
        row.draft_cached = c

    def _spec_round(self, k: int) -> bool:
        """One speculative round over the live rows: ONE draft-round
        dispatch (kmax proposals per row) + ONE verify dispatch (k+1
        target positions per row), then a host commit of each row's
        accepted prefix plus its resampled/bonus token.  Returns False
        when pages did not fit or the draft dispatch failed — the
        caller runs a plain decode step instead (speculation is
        opportunistic, never load-bearing for progress)."""
        kmax = self._spec_kmax()
        live = list(self._live)
        for row in live:
            if (not self._ensure_spec_pages(row, row.cached + k)
                    or not self._ensure_draft_ready(row, kmax)):
                self._spec_fallback()
                return False
        r = self._rows
        trash = self._pool.trash
        pending = onp.zeros((r,), onp.int32)
        catch = onp.zeros((r,), onp.int32)
        catch_on = onp.zeros((r,), bool)
        dtables = onp.full((r, self._draft_max_pages), trash, onp.int32)
        dlengths = onp.zeros((r,), onp.int32)
        tables = onp.full((r, self._max_pages), trash, onp.int32)
        lengths = onp.zeros((r,), onp.int32)
        temps = onp.zeros((r,), onp.float32)
        top_ks = onp.zeros((r,), onp.int32)
        top_ps = onp.ones((r,), onp.float32)
        seeds = onp.zeros((r,), onp.int32)
        for i, row in enumerate(live):
            pending[i] = row.pending
            d = row.draft_cached
            if row.cached - d == 1:
                # deficit 1 iff the previous round fully accepted: the
                # last proposal was committed but its KV never drafted
                catch_on[i] = True
                catch[i] = (row.req.prompt + row.req.out)[d]
            dtables[i, :len(row.draft_pages)] = row.draft_pages
            dlengths[i] = d
            tables[i, :len(row.pages)] = row.pages
            lengths[i] = row.cached
            (temps[i], top_ks[i], top_ps[i],
             seeds[i]) = _sampling_args(row.req.sampling)
        step_args: Dict[str, Any] = {"model": self.name,
                                     "rows": len(live), "k": k}
        traces = [row.req.trace_id for row in live
                  if row.req.trace_id is not None]
        if traces:
            step_args["trace_ids"] = traces
        drec = self._draft_round_program(kmax)
        vrec = self._verify_program(k)
        try:
            with _telemetry.span("decode.spec_round", cat="decode",
                                 args=step_args):
                t0 = time.perf_counter()
                self._pool.gate.acquire(self._priority)
                try:
                    with self._pool.exclusive(self._draft_geom):
                        dk, dv = self._pool.storage(self._draft_geom)
                        props, q_dist, dk, dv = drec(
                            self._draft_params, jnp.asarray(catch),
                            jnp.asarray(catch_on),
                            jnp.asarray(pending),
                            jnp.asarray(dtables),
                            jnp.asarray(dlengths), jnp.asarray(temps),
                            jnp.asarray(top_ks), jnp.asarray(top_ps),
                            jnp.asarray(seeds), dk, dv)
                        self._pool.set_storage(self._draft_geom,
                                               dk, dv)
                finally:
                    self._pool.gate.release()
                t1 = time.perf_counter()
                self._pool.gate.acquire(self._priority)
                try:
                    with self._pool.exclusive(self._geom):
                        kb, vb = self._pool.storage(self._geom)
                        n_acc, nxt, kb, vb = vrec(
                            self._params, jnp.asarray(pending),
                            props[:, :k], q_dist[:, :k],
                            jnp.asarray(tables), jnp.asarray(lengths),
                            jnp.asarray(temps), jnp.asarray(top_ks),
                            jnp.asarray(top_ps), jnp.asarray(seeds),
                            kb, vb)
                        # graftlint: disable=host-sync -- THE one host
                        # read per spec round: accepted counts, next
                        # tokens, and proposals feed the host commit
                        n_acc, nxt, props_h = (onp.asarray(n_acc),
                                               onp.asarray(nxt),
                                               onp.asarray(props))
                        self._pool.set_storage(self._geom, kb, vb)
                finally:
                    self._pool.gate.release()
                t2 = time.perf_counter()
        except BaseException as e:
            # a wedged/poisoned draft must never take plain decode
            # down with it: sticky-disable speculation and fall back
            # (pool storage is only replaced on success, and CPU runs
            # do not donate, so the buffers are intact)
            self._spec_autodisable("draft/verify dispatch failed",
                                   error=repr(e))
            self._spec_fallback()
            return False
        self._ema(("spec", "draft"), t1 - t0)
        self._ema(("spec_verify", k), t2 - t1)
        total_acc = 0
        committed = 0
        for i, row in enumerate(live):
            na = int(n_acc[i])
            total_acc += na
            c = row.cached
            toks = [int(props_h[i, j]) for j in range(na)]
            toks.append(int(nxt[i]))
            for t in toks:
                row.req.out.append(t)
                committed += 1
                if self._done(row):
                    break
            if not self._done(row):
                row.cached = c + 1 + na
                row.pending = row.req.out[-1]
            # the draft's KV stays valid exactly through the committed
            # prefix it already holds: positions c .. c+kmax-1 hold
            # [pending, d_1 .. d_{kmax-1}], of which 1 + min(na,
            # kmax-1) leading entries match the committed sequence —
            # rejected tails just rewind, never roll back
            row.draft_cached = c + 1 + min(na, kmax - 1)
        self._stats.inc("spec_rounds")
        self._stats.inc("spec_proposed", k * len(live))
        self._stats.inc("spec_accepted", total_acc)
        self._stats.inc("tokens_out", committed)
        _SPEC_STATS.inc("rounds")
        _SPEC_STATS.inc("proposed", k * len(live))
        _SPEC_STATS.inc("accepted", total_acc)
        # expected-acceptance EMA feeds the arbiter; the RATE EMA trips
        # the sticky low-acceptance cutoff (a garbage draft that never
        # agrees must not keep burning a draft+verify round per token)
        self._ema(("spec", "acc"), total_acc / max(len(live), 1))
        rate = total_acc / float(max(k * len(live), 1))
        self._spec_acc_ema = (rate if self._spec_acc_ema is None
                              else 0.7 * self._spec_acc_ema
                              + 0.3 * rate)
        self._spec_rounds_done += 1
        if self._spec_rounds_done >= 4 and self._spec_acc_ema < 0.2:
            self._spec_autodisable(
                "measured acceptance persistently low",
                acceptance=round(self._spec_acc_ema, 4))
        return True

    # -- speculative programs (namespace 'serving_spec') ---------------------
    def _draft_prefill_program(self, bucket: int):
        rec = self._spec_programs.lookup(("draft_prefill", bucket))
        if rec is not None:
            return rec
        return self._build_draft_prefill(bucket)

    def _build_draft_prefill(self, bucket: int):
        draft, pool, page = self._draft, self._pool, self._pool.page
        trash = pool.trash

        def draft_prefill_fn(dparams, tokens, length, table, k_pool,
                             v_pool):
            _pstore.count_trace("serving_spec")
            _logits, k, v = draft.prefill(dparams, tokens, length)
            pos = jnp.arange(bucket)
            valid = pos < length
            pidx = jnp.where(valid, table[pos // page], trash)
            slot = pos % page
            k_pool = k_pool.at[pidx, slot].set(k.transpose(1, 0, 2, 3))
            v_pool = v_pool.at[pidx, slot].set(v.transpose(1, 0, 2, 3))
            return k_pool, v_pool

        jitted = jax.jit(draft_prefill_fn,
                         donate_argnums=self._spec_prefill_donate)
        kspec, vspec = self._draft_pool_specs()
        args = (self._draft_param_specs(),
                jax.ShapeDtypeStruct((bucket,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((self._draft_max_pages,),
                                     jnp.int32),
                kspec, vspec)
        rec = _pstore.build(
            "serving_spec", jitted, args,
            label=f"{self.name}[draft_prefill b={bucket}]")
        self._spec_programs.insert(("draft_prefill", bucket), rec)
        return rec

    def _draft_round_program(self, kmax: int):
        rec = self._spec_programs.lookup(("draft_round", kmax))
        if rec is not None:
            return rec
        return self._build_draft_round(kmax)

    def _build_draft_round(self, kmax: int):
        """ONE program for the whole draft phase of a round: an
        optional masked catch-up step, then the pending token, then
        kmax-1 proposal feeds — kmax+1 unrolled draft decode steps, so
        a round costs exactly TWO dispatches (this + verify) however
        deep the speculation.  Proposals and their full sampling
        distributions stay on device into the verify program."""
        draft, pool, page = self._draft, self._pool, self._pool.page
        trash = pool.trash
        dmp = self._draft_max_pages
        r_total = self._rows
        nl, nh, hd = draft.n_layers, draft.n_heads, draft.head_dim

        def draft_round_fn(dparams, catch, catch_on, pending, tables,
                           lengths, temps, top_ks, top_ps, seeds,
                           k_pool, v_pool):
            _pstore.count_trace("serving_spec")
            rows = jnp.arange(r_total)

            def step(tok, pos, write, k_pool, v_pool):
                # one draft decode step: feed tok at per-row position
                # pos, scatter its KV (masked rows -> trash page),
                # return next-position logits.  Re-gathers the pool
                # each step — step j attends step j-1's KV
                k_ctx = k_pool[tables].reshape(
                    r_total, dmp * page, nl, nh, hd).transpose(
                    2, 0, 1, 3, 4)
                v_ctx = v_pool[tables].reshape(
                    r_total, dmp * page, nl, nh, hd).transpose(
                    2, 0, 1, 3, 4)
                logits, k_new, v_new = draft.decode(
                    dparams, tok, k_ctx, v_ctx, pos)
                pidx = jnp.where(
                    write,
                    tables[rows, jnp.minimum(pos // page, dmp - 1)],
                    trash)
                slot = pos % page
                k_pool = k_pool.at[pidx, slot].set(
                    k_new.transpose(1, 0, 2, 3))
                v_pool = v_pool.at[pidx, slot].set(
                    v_new.transpose(1, 0, 2, 3))
                return logits, k_pool, v_pool

            on = jnp.ones((r_total,), bool)
            # catch-up: after a FULLY accepted round the draft lags by
            # exactly one committed token — replay it (rows that do
            # not need it write to trash and do not advance)
            _, k_pool, v_pool = step(catch, lengths, catch_on,
                                     k_pool, v_pool)
            cur = lengths + catch_on.astype(jnp.int32)
            props, qs = [], []
            tok = pending
            for j in range(1, kmax + 1):
                logits, k_pool, v_pool = step(tok, cur + (j - 1), on,
                                              k_pool, v_pool)
                # proposal j sits at absolute position cur + j; gumbel
                # salt 3 keeps the draft's sampling noise independent
                # of the verify-side accept (salt 1) and resample
                # (salt 2) streams on the same position counter
                keys = jax.vmap(lambda sd, p: jax.random.fold_in(
                    token_key(sd, p), 3))(seeds, cur + j)
                d = jax.vmap(sample_token)(logits, temps, top_ks,
                                           top_ps, keys)
                q = jax.vmap(_sample_dist)(logits, temps, top_ks,
                                           top_ps)
                props.append(d)
                qs.append(q)
                tok = d
            return (jnp.stack(props, axis=1).astype(jnp.int32),
                    jnp.stack(qs, axis=1), k_pool, v_pool)

        kspec, vspec = self._draft_pool_specs()
        rows_i = jax.ShapeDtypeStruct((r_total,), jnp.int32)
        rows_f = jax.ShapeDtypeStruct((r_total,), jnp.float32)
        args = (self._draft_param_specs(), rows_i,
                jax.ShapeDtypeStruct((r_total,), jnp.bool_), rows_i,
                jax.ShapeDtypeStruct((r_total, dmp), jnp.int32),
                rows_i, rows_f, rows_i, rows_f, rows_i, kspec, vspec)
        jitted = jax.jit(draft_round_fn,
                         donate_argnums=self._spec_round_donate)
        rec = _pstore.build(
            "serving_spec", jitted, args,
            label=f"{self.name}[draft_round k={kmax}]")
        self._spec_programs.insert(("draft_round", kmax), rec)
        return rec

    def _verify_program(self, k: int):
        rec = self._spec_programs.lookup(("verify", k))
        if rec is not None:
            return rec
        return self._build_verify(k)

    def _build_verify(self, k: int):
        """The per-k fixed-shape verify program: ONE target dispatch
        scores all k+1 positions (pending + k proposals) via
        ``decode_chunk``, runs standard rejection sampling against the
        draft's proposal distributions (accept ``d_j`` iff
        ``u_j q_j(d_j) < p_j(d_j)``), resamples the first rejection
        from the residual ``norm(max(p - q, 0))`` — the bonus token on
        full acceptance unifies as a residual with ``q := 0`` — and
        scatters ONLY the accepted prefix's KV (rejected tails write
        the trash page: never committed, never rolled back).  The
        committed-token distribution is provably the target's own
        sampling distribution; under greedy both sides are one-hot and
        the chain is the exact argmax chain."""
        model, pool, page = self._model, self._pool, self._pool.page
        trash = pool.trash
        mp = self._max_pages
        r_total = self._rows
        s = k + 1

        def verify_fn(params, pending, props, q_dist, tables, lengths,
                      temps, top_ks, top_ps, seeds, k_pool, v_pool):
            _pstore.count_trace("serving_spec")
            rows = jnp.arange(r_total)
            k_ctx = k_pool[tables].reshape(
                r_total, mp * page, model.n_layers, model.n_heads,
                model.head_dim).transpose(2, 0, 1, 3, 4)
            v_ctx = v_pool[tables].reshape(
                r_total, mp * page, model.n_layers, model.n_heads,
                model.head_dim).transpose(2, 0, 1, 3, 4)
            toks = jnp.concatenate([pending[:, None], props], axis=1)
            logits, k_new, v_new = model.decode_chunk(
                params, toks, k_ctx, v_ctx, lengths)     # (R, S, V)
            # the target's own sampling distribution at every position
            p = jax.vmap(jax.vmap(_sample_dist,
                                  in_axes=(0, None, None, None))
                         )(logits, temps, top_ks, top_ps)
            # accept d_j iff u_j q_j(d_j) < p_j(d_j) (strict <, so a
            # zero-probability-under-p proposal NEVER survives);
            # n_acc = length of the accepted prefix
            jpos = lengths[:, None] + 1 + jnp.arange(k)[None, :]
            ukeys = jax.vmap(jax.vmap(
                lambda sd, pp: jax.random.fold_in(token_key(sd, pp), 1),
                in_axes=(None, 0)))(seeds, jpos)
            u = jax.vmap(jax.vmap(jax.random.uniform))(ukeys)
            qd = jnp.take_along_axis(q_dist, props[..., None],
                                     axis=2)[..., 0]     # (R, k)
            pd = jnp.take_along_axis(p[:, :k], props[..., None],
                                     axis=2)[..., 0]
            acc = (u * qd < pd).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(acc, axis=1), axis=1)
            # residual resampling at every candidate rejection point
            # (q_{k+1} := 0 makes the bonus draw plain p); an all-zero
            # residual (q covers p exactly) falls back to p
            qz = jnp.concatenate(
                [q_dist, jnp.zeros_like(q_dist[:, :1])], axis=1)
            res = jnp.maximum(p - qz, 0.0)
            tot = jnp.sum(res, axis=-1, keepdims=True)
            dist = jnp.where(tot > 0.0,
                             res / jnp.where(tot > 0.0, tot, 1.0), p)
            rpos = lengths[:, None] + 1 + jnp.arange(s)[None, :]
            rkeys = jax.vmap(jax.vmap(
                lambda sd, pp: jax.random.fold_in(token_key(sd, pp), 2),
                in_axes=(None, 0)))(seeds, rpos)
            gum = jax.vmap(jax.vmap(
                lambda kk: jax.random.gumbel(kk, (model.vocab,))
                ))(rkeys)
            cand = jnp.argmax(jnp.log(dist) + gum, axis=-1)  # (R, S)
            nxt = cand[rows, n_acc]
            # KV scatter: chunk position i commits iff i <= n_acc
            # (pending always; then the accepted proposals)
            keep = jnp.arange(s)[None, :] <= n_acc[:, None]
            wpos = lengths[:, None] + jnp.arange(s)[None, :]
            pidx = jnp.where(
                keep,
                tables[rows[:, None],
                       jnp.minimum(wpos // page, mp - 1)],
                trash)
            slot = wpos % page
            # (L, R, S, H, D) -> (R, S, L, H, D) rows
            k_pool = k_pool.at[pidx, slot].set(
                k_new.transpose(1, 2, 0, 3, 4))
            v_pool = v_pool.at[pidx, slot].set(
                v_new.transpose(1, 2, 0, 3, 4))
            return (n_acc.astype(jnp.int32), nxt.astype(jnp.int32),
                    k_pool, v_pool)

        kspec, vspec = self._pool_specs()
        rows_i = jax.ShapeDtypeStruct((r_total,), jnp.int32)
        rows_f = jax.ShapeDtypeStruct((r_total,), jnp.float32)
        args = (self._param_specs(), rows_i,
                jax.ShapeDtypeStruct((r_total, k), jnp.int32),
                jax.ShapeDtypeStruct((r_total, k, int(model.vocab)),
                                     jnp.float32),
                jax.ShapeDtypeStruct((r_total, mp), jnp.int32),
                rows_i, rows_f, rows_i, rows_f, rows_i, kspec, vspec)
        jitted = jax.jit(verify_fn,
                         donate_argnums=self._spec_round_donate)
        rec = _pstore.build("serving_spec", jitted, args,
                            label=f"{self.name}[verify k={k}]")
        self._spec_programs.insert(("verify", k), rec)
        return rec

    def _donated(self, *argnums: int) -> Tuple[int, ...]:
        # pool buffers update in place on an accelerator; the CPU test
        # backend keeps donation off (the cached_step idiom)
        return argnums if self._device.platform != "cpu" else ()

    @property
    def _spec_prefill_donate(self) -> Tuple[int, ...]:
        return self._donated(4, 5)

    @property
    def _spec_round_donate(self) -> Tuple[int, ...]:
        return self._donated(10, 11)

    @staticmethod
    def _spec_of(a):
        # the spec carries the buffer's (committed) placement, so the
        # AOT executable is compiled for the engine's device, not for
        # whatever the process default happens to be
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

    def _draft_pool_specs(self):
        k, v = self._pool.storage(self._draft_geom)
        return self._spec_of(k), self._spec_of(v)

    def _draft_param_specs(self):
        return jax.tree_util.tree_map(self._spec_of, self._draft_params)

    # -- shapes / specs ------------------------------------------------------
    @property
    def _donate(self) -> Tuple[int, ...]:
        return self._donated(8, 9)

    @property
    def _chunk_donate(self) -> Tuple[int, ...]:
        # chunk prefill carries (offset, length): pool buffers sit one
        # argument later
        return self._donated(9, 10)

    def _pool_specs(self):
        k, v = self._pool.storage(self._geom)
        return self._spec_of(k), self._spec_of(v)

    def _param_specs(self):
        return jax.tree_util.tree_map(self._spec_of, self._params)

    @staticmethod
    def _sampling_specs():
        # (temperature, top_k, top_p, seed) scalar traced arguments
        return (jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32))

    def _prefill_specs(self, bucket: int):
        kspec, vspec = self._pool_specs()
        return (self._param_specs(),
                jax.ShapeDtypeStruct((bucket,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((self._max_pages,), jnp.int32),
                *self._sampling_specs(),
                kspec, vspec)

    def _chunk_specs(self, bucket: int):
        kspec, vspec = self._pool_specs()
        return (self._param_specs(),
                jax.ShapeDtypeStruct((bucket,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),      # offset
                jax.ShapeDtypeStruct((), jnp.int32),      # length
                jax.ShapeDtypeStruct((self._max_pages,), jnp.int32),
                *self._sampling_specs(),
                kspec, vspec)

    def _decode_specs(self):
        kspec, vspec = self._pool_specs()
        rows = jax.ShapeDtypeStruct((self._rows,), jnp.int32)
        return (self._param_specs(),
                rows,
                jax.ShapeDtypeStruct((self._rows, self._max_pages),
                                     jnp.int32),
                rows,
                jax.ShapeDtypeStruct((self._rows,), jnp.float32),
                rows,   # top_k
                jax.ShapeDtypeStruct((self._rows,), jnp.float32),
                rows,   # seed
                kspec, vspec)

    # -- retire / deliver ----------------------------------------------------
    def _done(self, row: _Row) -> bool:
        req = row.req
        return (len(req.out) >= req.max_new
                or (req.eos is not None and req.out
                    and req.out[-1] == req.eos))

    def _retire_finished(self) -> None:
        for row in [x for x in self._live if self._done(x)]:
            self._live.remove(row)
            self._deliver(row)

    def _release(self, row: _Row) -> None:
        if row.pages:
            self._pool.free(row.pages)
            row.pages = []
        if row.draft_pages:
            self._pool.free(row.draft_pages)
            row.draft_pages = []
            row.draft_cached = 0

    def _deliver(self, row: _Row) -> None:
        self._release(row)               # pages free THIS iteration
        self._stats.inc("delivered")
        row.req.t_done = time.monotonic()
        row.req.event.set()

    def _ema(self, key, secs: float, alpha: float = 0.3) -> None:
        prev = self._cost.get(key)
        self._cost[key] = secs if prev is None \
            else (1 - alpha) * prev + alpha * secs

    # -- ahead-of-time warmup ------------------------------------------------
    def warmup(self, max_len: Optional[int] = None) -> int:
        """Compile the bounded program set — one prefill per bucket of
        the ``MXNET_SHAPE_BUCKETS`` grid (pow2 spans 1..``max_len``,
        default ``model.max_seq``; an explicit grid compiles verbatim)
        plus THE decode program — from abstract shapes at deploy time,
        off the request path (with ``MXNET_PROGRAM_CACHE_DIR`` they
        persist for the next process).  Returns programs compiled
        (0 = already warm)."""
        if self._closed:
            raise RuntimeError("GenerativeEngine is closed")
        cap = int(max_len if max_len is not None else self._model.max_seq)
        cap = min(cap, int(self._model.max_seq))
        if not self._policy.enabled:
            grid: List[int] = [cap]
        elif self._policy.buckets() is not None:
            grid = [b for b in self._policy.buckets() if b <= cap]
        else:
            grid, b = [], 1
            while b <= cap:
                grid.append(b)
                b <<= 1
        compiled = 0
        for b in grid:
            if self._programs.lookup(("prefill", b)) is None:
                self._build_prefill(b)
                compiled += 1
        if self._programs.lookup(("decode",)) is None:
            self._build_decode()
            compiled += 1
        if self._draft is not None:
            # the spec grid: draft prefill per bucket + ONE draft
            # round + one verify per k — compiled here so a spec storm
            # holds 0 retraces exactly like the plain lane
            kmax = self._spec_kmax()
            dcap = min(cap, int(self._draft.max_seq))
            for b in grid:
                if b > dcap:
                    continue
                if self._spec_programs.lookup(
                        ("draft_prefill", b)) is None:
                    self._build_draft_prefill(b)
                    compiled += 1
            if self._spec_programs.lookup(
                    ("draft_round", kmax)) is None:
                self._build_draft_round(kmax)
                compiled += 1
            ks = (range(1, kmax + 1)
                  if self._spec_setting() == "auto" else [kmax])
            for kk in ks:
                if self._spec_programs.lookup(("verify", kk)) is None:
                    self._build_verify(kk)
                    compiled += 1
        self._stats.inc("warmup_programs", compiled)
        return compiled
