"""Shape-bucketed compiled inference with dynamic micro-batching — the
serving analog of ``cached_step.TrainStep``.

The reference funnels all inference through ``CachedOp``: one compiled
program per model, dispatched per request, re-planned for every distinct
input shape.  On a variable-length request stream that means unbounded
retraces — exactly the padding/shape-sensitivity cost "A Learned
Performance Model for TPUs" (2008.01040) quantifies, and which
"Operator Fusion in XLA" (2301.13062) shows is only recovered when work
stays inside one fused program.  This module bounds the program set:

1. **Shape bucketing** (:class:`BucketPolicy`, ``MXNET_SHAPE_BUCKETS``):
   variable axes are padded up to a bucket grid (powers-of-two by
   default, or an explicit user list) so an arbitrary-length stream hits
   a BOUNDED set of XLA programs — steady state: 0 retraces.  Results
   are sliced back to true lengths.  Padding is only trusted after a
   one-time **verify** per padded signature: the padded-and-sliced
   output must be bit-exact against the unpadded eager forward
   (``MXNET_SERVE_VERIFY``).  Models whose outputs couple across the
   padded axis — mean-style reductions over a padded length, outputs
   whose shape follows the input length — FAIL that check and the
   engine explicitly refuses bucketing (sticky, reason recorded in
   :attr:`ServingEngine.bucket_refused`), falling back to exact-shape
   single-request programs.  Correct always; fast when the model allows.

2. **Dynamic micro-batching** (:class:`ServingEngine`): concurrent
   :meth:`ServingEngine.infer` calls enqueue; a stager thread coalesces
   them into ONE padded batch per dispatch (``MXNET_SERVE_MAX_BATCH`` /
   ``MXNET_SERVE_MAX_DELAY_US``), stages host arrays to device through
   the same one-``device_put``-per-batch path the DataLoader's
   ``_wrap`` staging uses, and hands a DOUBLE-BUFFERED queue (depth 2)
   to the dispatcher thread — batch N+1 stages while batch N's program
   runs.  Results de-interleave back to per-request slices.  The
   dispatch runs under the ``serving.infer`` fault site (PR-2
   ``faults.py``): an injected timeout/transient failure falls back to
   single-request processing — a request is NEVER dropped (an error is
   delivered to exactly the request that caused it).

3. **Observability**: module counters (:func:`trace_count`,
   :func:`dispatch_count`, :func:`bucket_stats`) mirror the
   ``cached_step`` idiom; per-engine :meth:`ServingEngine.stats` adds
   coalescing ratios and p50/p99 request latency.

The bucket policy is shared with training: ``Trainer.compile_step(...,
bucket=True)`` and ``HybridBlock.hybridize(bucket=True)`` pad through
the same :class:`BucketPolicy`, so variable-length training stops
blowing the PR-3 program cache too (see ``cached_step.py`` /
``gluon/block.py``).

This module serves ONE-SHOT inference (a request is one forward).
Autoregressive GENERATION — continuous batching, the paged KV-cache
with its content-addressed prefix cache (``MXNET_PREFIX_CACHE``:
hash-keyed copy-on-write pages so shared prompts prefill once), and
multi-model SLO-aware admission — lives in its sibling
``serving_decode.py``, which generalizes :class:`BucketPolicy` along
the sequence axis for its prefill program grid.  One-shot inference
has no KV state, so nothing here content-addresses; the bucket grid
below is the part the two stacks share.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from . import autograd
from . import config as _config
from . import faults as _faults
from . import preemption as _preemption
from . import program_store as _pstore
from . import random as _random
from . import telemetry as _telemetry
from .context import current_context

__all__ = ["BucketPolicy", "ServingEngine", "trace_count", "dispatch_count",
           "bucket_stats", "reset_counters"]

# observability, mirroring cached_step: serving programs live in the
# ProgramStore 'serving' namespace — traces bump when a serving program
# body is (re)traced, dispatches per compiled launch, and hits/misses
# track how the padded-shape program cache behaves (hit = the bucketed
# signature already had a program).  The functions below are views over
# that surface.  The CI gate (tools/check_dispatch_budget.py) asserts
# retraces go to 0 over a variable-length stream once every bucket is
# warm.
_NS = _pstore.namespace("serving")


def trace_count() -> int:
    return _NS.traces


def dispatch_count() -> int:
    return _NS.dispatches


def bucket_stats() -> Dict[str, int]:
    return {"hits": _NS.hits, "misses": _NS.misses}


def reset_counters() -> None:
    _NS.reset()


# ---------------------------------------------------------------------------
# Bucket policy
# ---------------------------------------------------------------------------
class BucketPolicy:
    """Maps a dynamic axis length to its padded bucket length.

    Spec (``MXNET_SHAPE_BUCKETS``):

    - ``"pow2"`` (default) — round up to the next power of two;
    - ``"none"`` — bucketing disabled (every shape compiles exactly);
    - ``"8,16,32,64"`` — explicit ascending grid; a length ABOVE the
      largest bucket returns ``None`` (caller falls back to the exact
      shape — the above-largest-bucket contract, counted by the engine).
    """

    def __init__(self, spec: Optional[str] = None):
        spec = (spec if spec is not None
                else _config.get("MXNET_SHAPE_BUCKETS")).strip().lower()
        self.spec = spec
        self._grid: Optional[Tuple[int, ...]] = None
        if spec in ("pow2", "none"):
            pass
        else:
            try:
                grid = tuple(sorted({int(t) for t in spec.split(",") if t}))
            except ValueError:
                raise ValueError(
                    f"MXNET_SHAPE_BUCKETS={spec!r}: expected 'pow2', "
                    "'none', or a comma list of ints")
            if not grid or grid[0] < 1:
                raise ValueError(
                    f"MXNET_SHAPE_BUCKETS={spec!r}: buckets must be >= 1")
            self._grid = grid

    @property
    def enabled(self) -> bool:
        return self.spec != "none"

    def buckets(self) -> Optional[Tuple[int, ...]]:
        """The explicit grid, or None for pow2/none."""
        return self._grid

    def bucket(self, n: int) -> Optional[int]:
        """Padded length for a true length ``n``; ``None`` = no bucket
        covers it (explicit grid only) — use the exact shape."""
        if not self.enabled:
            return n
        if self._grid is None:           # pow2
            b = 1
            while b < n:
                b <<= 1
            return b
        for b in self._grid:
            if b >= n:
                return b
        return None

    def __repr__(self):
        return f"BucketPolicy({self.spec!r})"


def pad_axis0(data: "jax.Array", target: int) -> "jax.Array":
    """Zero-pad a leaf's leading axis up to ``target`` rows."""
    n = data.shape[0]
    if n == target:
        return data
    pads = [(0, target - n)] + [(0, 0)] * (data.ndim - 1)
    return jnp.pad(data, pads)


def pad_to_shape(data: "jax.Array", shape: Sequence[int]) -> "jax.Array":
    """Zero-pad trailing on every axis up to ``shape``."""
    if tuple(data.shape) == tuple(shape):
        return data
    pads = [(0, t - s) for s, t in zip(data.shape, shape)]
    return jnp.pad(data, pads)


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------
class _Request:
    __slots__ = ("leaves", "struct", "rows", "args", "event", "result",
                 "error", "t_enqueue", "t_done", "trace_id")

    def __init__(self, leaves, struct, rows, args):
        self.leaves = leaves          # raw jax arrays, leading batch axis
        self.struct = struct
        self.rows = rows
        self.args = args              # original NDArray args (fallback)
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.t_enqueue = time.monotonic()
        self.t_done = 0.0
        # ISSUE-15 request identity: minted (or inherited from the
        # router) at infer() entry; the stager/dispatcher threads batch
        # many requests into one dispatch, so the batched span carries
        # the whole group's ids as args.trace_ids
        self.trace_id: Optional[str] = None


class ServingEngine:
    """Compiled inference engine over one model: request coalescing +
    shape-bucketed padded programs + de-interleaved results.

    ``engine = ServingEngine(net); out = engine.infer(x)`` — ``infer``
    is thread-safe and blocking; concurrent callers coalesce into one
    padded dispatch.  ``net`` runs in inference mode (``training=False``,
    recording off) through the same staging machinery as ``hybridize()``
    (``gluon.block._stage_fn``), one jitted program per bucketed input
    signature with an LRU cap (``MXNET_FORWARD_CACHE``).
    """

    def __init__(self, net, max_batch: Optional[int] = None,
                 max_delay_us: Optional[int] = None,
                 verify: Optional[bool] = None,
                 policy: Optional[BucketPolicy] = None,
                 mesh=None):
        self._net = net
        self._policy = policy or BucketPolicy()
        # replicated SPMD inference (the kvstore='tpu' serving
        # counterpart): with a mesh, parameters replicate across the
        # 'dp' axis and each coalesced batch shards over it, so
        # throughput scales with the same mesh the train step uses.
        # Still one compiled launch per dispatched batch — the SPMD
        # partitioner fans the work out, not the host.  An indivisible
        # batch axis replicates (loud, spmd.replicated_batch_count);
        # the pow2 bucket grid keeps coalesced batches divisible.
        self._mesh = mesh
        self._max_batch = (max_batch if max_batch is not None
                           else _config.get("MXNET_SERVE_MAX_BATCH"))
        self._max_delay = (max_delay_us if max_delay_us is not None
                           else _config.get("MXNET_SERVE_MAX_DELAY_US")) / 1e6
        self._verify = (bool(_config.get("MXNET_SERVE_VERIFY"))
                        if verify is None else bool(verify))
        # this engine's keyspace in the ProgramStore 'serving'
        # namespace: shared eviction (cap MXNET_FORWARD_CACHE /
        # MXNET_PROGRAM_CACHE_CAPS) + shared metrics, per-engine keys
        self._programs = _pstore.scope("serving")
        self._verified: set = set()
        # sticky refusals: verify mismatch (or an in-batch mutation)
        # disables padding AND coalescing — outputs that couple across
        # the padded/coalesced axis cannot be sliced apart correctly
        self.bucket_refused: Optional[str] = None
        # dynamic-axis tracking: (struct_key, leaf, axis) -> sizes seen.
        # An axis becomes dynamic once two sizes are observed; only
        # dynamic non-batch axes are padded (static axes stay exact, so
        # a fixed 224x224 CNN never gets its image padded to 256).
        self._axis_seen: Dict[Tuple, set] = {}
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._requests: "deque[_Request]" = deque()
        # staging buffer: stager fills, dispatcher drains — the next
        # batch's pad/concat/device staging overlaps the current
        # program's execution.  Depth follows the pipeline engine's
        # prefetch knob (MXNET_ENGINE_PREFETCH, floor 2 so the classic
        # double buffer survives depth 0/NaiveEngine — serving stays
        # concurrent either way; only the TRAIN loop goes synchronous
        # under the naive escape hatch).
        import queue as _queue

        from . import engine as _engine

        self._staged: "_queue.Queue" = _queue.Queue(
            maxsize=max(2, _engine.prefetch_depth()))
        self._busy = 0           # groups popped but not yet staged
        _engine.register_drainable(self)
        self._threads: List[threading.Thread] = []
        self._closed = False
        self._draining = False    # per-replica drain (ISSUE 17)
        self._latencies: "deque[float]" = deque(maxlen=8192)
        # per-engine counters live in the telemetry registry under a
        # unique instance prefix (family 'serving.engine'); stats()
        # still hands out plain ints via the Mapping view
        self._stats = _telemetry.CounterGroup(
            _telemetry.instance_name("serving.engine"),
            ("requests", "batches", "coalesced", "padded_rows",
             "true_rows", "bucket_fallbacks", "single_fallbacks",
             "verify_runs", "verify_ulp_accepts", "warmup_programs",
             "shed_draining", "shed_deadline"),
            doc="ServingEngine per-instance counters",
            family="serving.engine")
        # load() fields double as registered computed gauges (ISSUE
        # 17): balancer and autoscaler read one surface
        _telemetry.register_load_gauges(self, self._stats.prefix)

    # -- public ------------------------------------------------------------
    def infer(self, *args):
        """Run one inference request (leading batch axis on every array
        argument); blocks until the coalesced dispatch delivers.  Raises
        whatever the model raised for THIS request — never drops.

        Admission mints (or inherits, when routed) the ISSUE-15 request
        trace: the admission/shed events, the request-lifecycle span,
        and the coalesced dispatch's span all stamp one trace_id."""
        with _telemetry.trace_scope():
            return self._infer_traced(args)

    def _infer_traced(self, args):
        from .gluon import block as _gb
        from .ndarray import ndarray as _ndmod

        if self._closed:
            raise RuntimeError("ServingEngine is closed")
        if _preemption.draining() or self._draining:
            # preemption notice taken (or this ONE replica is leaving
            # the fleet, ISSUE 17): refuse IMMEDIATELY and typed —
            # accepted requests still deliver, new ones never park
            # toward the grace deadline
            self._stats.inc("shed_draining")
            _telemetry.event("shed", self._stats.prefix,
                             shed_kind="draining",
                             reason="preemption drain")
            _faults.record_event("serving.infer", "shed",
                                 kind="draining",
                                 reason="preemption drain")
            raise _faults.ShedError(
                "serving engine draining after a preemption notice; "
                "re-queue this request after the restart",
                kind="draining")
        # host (numpy) request payloads stage to device HERE — one
        # device_put per leaf, the DataLoader._wrap staging contract —
        # so they become real batch leaves, never baked trace constants
        args = _stage_host(args)
        self._ensure_initialized(args)
        leaves, struct = _gb._flatten_args(args)
        if not leaves:
            raise ValueError("infer() needs at least one array argument")
        for l in leaves:
            if len(l.shape) < 1:
                raise ValueError(
                    "every infer() array argument needs a leading batch "
                    "axis (got a 0-d array)")
        rows = int(leaves[0].shape[0])
        for l in leaves:
            if int(l.shape[0]) != rows:
                raise ValueError(
                    "all infer() arguments must share the leading batch "
                    f"axis (got {rows} vs {int(l.shape[0])})")
        if rows < 1:
            raise ValueError("infer() needs at least one row")
        req = _Request([l._data for l in leaves], struct, rows, args)
        req.trace_id = _telemetry.current_trace()
        if req.trace_id is not None:
            _telemetry.event("admit", self._stats.prefix, rows=rows)
        self._observe_axes(req)
        # the request's deadline budget (faults.deadline_scope on the
        # caller's thread — the router threads one per request):
        # admission + queue wait + dispatch all draw from it
        rem_us = _faults.deadline_remaining_us()
        if rem_us is not None and rem_us <= 0:
            self._stats.inc("shed_deadline")
            _telemetry.event("shed", self._stats.prefix,
                             shed_kind="deadline",
                             reason="budget spent at admission")
            _faults.record_event("serving.infer", "shed", kind="deadline",
                                 reason="budget spent at admission")
            raise _faults.ShedError(
                "deadline budget already spent at admission",
                kind="deadline")
        until = (time.monotonic() + rem_us / 1e6
                 if rem_us is not None else None)
        with self._cv:
            self._start_threads()
            self._requests.append(req)
            self._cv.notify_all()
        if until is None:
            delivered = req.event.wait(timeout=300.0)
        else:
            delivered = req.event.wait(
                timeout=max(0.0, until - time.monotonic()))
        if not delivered:
            if until is not None:
                # budget spent while queued/staged: withdraw if still
                # queued and hand back typed — NEVER a hang (a staged
                # batch still delivers to the other members)
                with self._cv:
                    try:
                        self._requests.remove(req)
                    except ValueError:
                        pass
                self._stats.inc("shed_deadline")
                _telemetry.event("shed", self._stats.prefix,
                                 shed_kind="deadline",
                                 reason="budget exhausted in queue")
                _faults.record_event("serving.infer", "shed",
                                     kind="deadline",
                                     reason="budget exhausted in queue")
                raise _faults.ShedError(
                    "deadline budget exhausted before the coalesced "
                    "dispatch delivered", kind="deadline")
            raise _faults.DeadlineExceeded(
                "serving request not delivered within 300s (engine "
                "threads wedged?)")
        if req.error is not None:
            raise req.error
        self._latencies.append(req.t_done - req.t_enqueue)
        if req.trace_id is not None:
            _telemetry.event("retire", self._stats.prefix, rows=req.rows)
        # request lifecycle span (admit -> dispatch -> deliver): the
        # serving leg of the unified chrome-trace timeline
        off = _telemetry.monotonic_offset_ns()     # to the spans' clock
        _telemetry.record_span(
            "serving.request", "serving",
            int(req.t_enqueue * 1e9) + off, int(req.t_done * 1e9) + off,
            args={"rows": req.rows, "engine": self._stats.prefix})
        return req.result

    def spans(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Recent serving span records (request lifecycles + batched
        dispatches) from the unified telemetry span buffer."""
        return _telemetry.spans(cat="serving", limit=limit)

    def load(self) -> Dict[str, float]:
        """Cheap live-load signals for a balancer (the replica router's
        scoring input): queued requests + staged-but-undispatched
        batches.  No host syncs."""
        with self._lock:
            depth = len(self._requests)
            busy = self._busy
        return {
            "queue_depth": float(depth),
            "in_flight": float(busy + self._staged.qsize()),
            "pool_pressure": 0.0,          # no KV pool on this path
        }

    def stats(self) -> Dict[str, Any]:
        """Counters + latency percentiles (``p50_us``/``p99_us``)."""
        out = dict(self._stats)
        out["programs"] = len(self._programs)
        out["bucket_refused"] = self.bucket_refused
        out["mesh_devices"] = (self._mesh.devices.size
                               if self._mesh is not None else 1)
        lat = sorted(self._latencies)
        if lat:
            out["p50_us"] = lat[len(lat) // 2] * 1e6
            out["p99_us"] = lat[min(len(lat) - 1,
                                    int(len(lat) * 0.99))] * 1e6
            out["mean_us"] = sum(lat) / len(lat) * 1e6
        else:
            out["p50_us"] = out["p99_us"] = out["mean_us"] = 0.0
        return out

    def begin_drain(self) -> None:
        """Per-replica drain (the router's ``drain_replica`` handback
        hook, ISSUE 17): new admissions on this ONE engine shed typed
        ``draining`` (the router fails them over to a SERVING
        replica); already-accepted requests still deliver.  The
        process-wide analog is the preemption notice."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()

    def drain(self, timeout: float = 60.0) -> None:
        """engine.waitall() hook: block until every accepted request has
        been staged, dispatched, and delivered (queues empty, no batch
        in flight)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                idle = not self._requests and self._busy == 0
            if idle and self._staged.unfinished_tasks == 0:
                return
            time.sleep(0.002)

    def close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        try:
            self._staged.put_nowait(None)
        except Exception:
            pass
        for t in self._threads:
            t.join(timeout=2.0)
        self._threads = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            if not self._closed:
                self.close()
        except Exception:
            pass

    # -- setup -------------------------------------------------------------
    def _ensure_initialized(self, args):
        params = self._net.collect_params()
        if any(p._data is None for p in params.values()):
            # one eager inference completes deferred init, exactly like
            # the first call of a hybridized block
            with autograd.pause():
                self._net(*args)

    def _start_threads(self):
        if self._threads or self._closed:
            return
        stager = threading.Thread(target=self._stage_loop, daemon=True,
                                  name="mxnet-serving-stager")
        dispatcher = threading.Thread(target=self._dispatch_loop, daemon=True,
                                      name="mxnet-serving-dispatcher")
        self._threads = [stager, dispatcher]
        stager.start()
        dispatcher.start()

    def _observe_axes(self, req: _Request):
        skey = _struct_key_of(req.struct)
        for li, arr in enumerate(req.leaves):
            for ax in range(1, arr.ndim):
                seen = self._axis_seen.setdefault((skey, li, ax), set())
                if len(seen) < 64:
                    seen.add(int(arr.shape[ax]))

    def _dynamic_axes(self, skey, li, ndim) -> List[int]:
        return [ax for ax in range(1, ndim)
                if len(self._axis_seen.get((skey, li, ax), ())) > 1]

    # -- stager: coalesce + pad + stage -------------------------------------
    def _stage_loop(self):
        while True:
            try:
                group = self._collect_group()
            except BaseException:            # keep the stager alive
                continue
            if group is None:
                return                       # closed
            # _busy covers the popped-but-not-yet-staged window so
            # drain() cannot declare the engine idle mid-staging
            self._busy += 1
            try:
                try:
                    staged = self._stage_group(group)
                except BaseException as e:   # staging failed: per-request
                    self._deliver_fallback(group, cause=e)
                    continue
                self._staged.put(staged)
            finally:
                self._busy -= 1

    def _collect_group(self) -> Optional[List[_Request]]:
        """Pop a head request, then coalesce compatible followers until
        max_batch rows or the max-delay window closes."""
        with self._cv:
            while not self._requests and not self._closed:
                self._cv.wait(timeout=0.1)
            if self._closed and not self._requests:
                return None
            group = [self._requests.popleft()]
            if self.bucket_refused is not None:
                return group                 # single-request mode
            rows = group[0].rows
            deadline = group[0].t_enqueue + self._max_delay
            while rows < self._max_batch:
                if not self._requests:
                    remain = deadline - time.monotonic()
                    if remain <= 0:
                        break
                    self._cv.wait(timeout=remain)
                    if not self._requests:
                        if time.monotonic() >= deadline:
                            break
                        continue
                head = self._requests[0]
                if not self._compatible(group[0], head):
                    break                    # preserve order; next round
                if rows + head.rows > self._max_batch:
                    break
                group.append(self._requests.popleft())
                rows += head.rows
            return group

    def _compatible(self, a: _Request, b: _Request) -> bool:
        if _struct_key_of(a.struct) != _struct_key_of(b.struct):
            return False
        if len(a.leaves) != len(b.leaves):
            return False
        skey = _struct_key_of(a.struct)
        for li, (la, lb) in enumerate(zip(a.leaves, b.leaves)):
            if la.ndim != lb.ndim or la.dtype != lb.dtype:
                return False
            dyn = set(self._dynamic_axes(skey, li, la.ndim))
            for ax in range(1, la.ndim):
                if ax not in dyn and la.shape[ax] != lb.shape[ax]:
                    return False
        return True

    def _stage_group(self, group: List[_Request]):
        """Pad every request's dynamic axes to the group target, concat
        along the batch axis, pad the batch axis to its bucket.  Device
        work (pad/concat are device ops on already-staged leaves; host
        numpy inputs took one device_put in infer's array wrap) — this
        runs on the stager thread, overlapping the dispatcher."""
        skey = _struct_key_of(group[0].struct)
        rows = sum(r.rows for r in group)
        pad_active = False
        bucket = rows
        if self._policy.enabled and self.bucket_refused is None:
            b = self._policy.bucket(rows)
            if b is None:                    # above the largest bucket
                self._stats.inc("bucket_fallbacks")
            else:
                bucket = b
            pad_active = bucket != rows
        batched = []
        for li in range(len(group[0].leaves)):
            ndim = group[0].leaves[li].ndim
            dyn = self._dynamic_axes(skey, li, ndim)
            target = list(group[0].leaves[li].shape)
            for ax in dyn:
                size = max(int(r.leaves[li].shape[ax]) for r in group)
                tb = self._policy.bucket(size) \
                    if (self._policy.enabled and
                        self.bucket_refused is None) else size
                target[ax] = size if tb is None else tb
                if target[ax] != size or any(
                        int(r.leaves[li].shape[ax]) != size for r in group):
                    pad_active = True
            parts = [pad_to_shape(r.leaves[li],
                                  [r.rows] + target[1:]) for r in group]
            arr = parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)
            batched.append(pad_axis0(arr, bucket))
        self._stats.inc("padded_rows", bucket)
        self._stats.inc("true_rows", rows)
        return (group, batched, rows, pad_active)

    # -- dispatcher ---------------------------------------------------------
    def _dispatch_loop(self):
        while True:
            item = self._staged.get()
            if item is None:
                self._staged.task_done()
                return
            group, batched, rows, pad_active = item
            try:
                # the serving fault site: an injected timeout/transient
                # here models a wedged/poisoned batched dispatch —
                # recovery is per-request fallback, never a drop
                _faults.inject("serving.infer")
                self._dispatch(group, batched, rows, pad_active)
            except BaseException as e:
                _faults.record_event("serving.infer", "fallback", e,
                                     requests=len(group))
                self._stats.inc("single_fallbacks", len(group))
                self._deliver_fallback(group, cause=e)
            finally:
                # task_done pairs every put so drain()'s unfinished-
                # tasks check sees a truly empty pipeline
                self._staged.task_done()

    def _dispatch(self, group, batched, rows, pad_active):
        from .gluon import block as _gb
        from .ndarray import ndarray as _ndmod

        first = group[0]
        ctx = (first.args[0].ctx if first.args and
               hasattr(first.args[0], "ctx") else current_context())
        flavor = _ndmod._flavor_of(
            [a for a in first.args if hasattr(a, "_data")])
        sig = (_struct_key_of(first.struct),
               tuple((tuple(b.shape), str(b.dtype)) for b in batched),
               _ndmod._amp_generation, ctx, flavor)
        rec = self._programs.lookup(sig)
        if rec is None:
            built = self._build_jit(first.struct, ctx, flavor)
            names, params = built[1], built[2]
        else:
            names, params = rec.meta[0], rec.meta[1]

        if self._mesh is not None:
            from .parallel import spmd as _spmd

            rep = _spmd.replicated(self._mesh)
            for n in names:
                d = params[n]._data[0]
                new = _spmd.ensure_placed(d._data, rep)
                if new is not d._data:
                    d._set_data(new)      # once; steady state passes through
            batched = [_spmd.put_batch(b, self._mesh) for b in batched]
        param_arrays = [params[n]._data[0]._data for n in names]
        if rec is None:
            # one code path with warmup(): trace + AOT-compile through
            # the store (persisting under MXNET_PROGRAM_CACHE_DIR), then
            # dispatch the owned executable
            jitted = built[0]
            rec = _pstore.build(
                "serving", jitted,
                (batched, param_arrays, jax.random.PRNGKey(0)),
                meta=built[1:], label=type(self._net).__name__)
            self._programs.insert(sig, rec)
        _names, _params, out_struct, mutated_names = rec.meta
        span_args = {"rows": int(batched[0].shape[0]),
                     "requests": len(group)}
        traces = [r.trace_id for r in group if r.trace_id is not None]
        if traces:
            # a coalesced dispatch serves MANY requests: the span lists
            # every member's trace so telemetry.trace(id) stitches it
            # into each one's lifecycle
            span_args["trace_ids"] = traces
        with _telemetry.span("serving.dispatch", cat="serving",
                             args=span_args):
            out_arrays, mut_vals = rec(batched, param_arrays,
                                       _random.next_key())
        self._stats.inc("batches")
        self._stats.inc("requests", len(group))
        self._stats.inc("coalesced", len(group) - 1)

        transformed = pad_active or len(group) > 1
        if mutated_names and transformed:
            # a forward that mutates state (running stats etc.) cannot
            # absorb pad rows / foreign requests into that state —
            # refuse and re-run each request alone (mutation NOT written)
            raise _BucketRefused(
                f"forward mutates parameter(s) {mutated_names} — padding/"
                "coalescing would fold pad rows into live state")
        for n, v in zip(mutated_names, mut_vals):
            params[n]._data[0]._set_data(v)

        padded_n = batched[0].shape[0]
        if transformed:
            for o in out_arrays:
                if o.ndim < 1 or int(o.shape[0]) != padded_n:
                    raise _BucketRefused(
                        "output does not carry the batch axis (shape "
                        f"{tuple(o.shape)} vs batch {padded_n}) — "
                        "cannot slice per-request results")
        if self._verify and transformed and sig not in self._verified:
            self._verify_group(group, out_arrays, padded_n)
            self._verified.add(sig)
        start = 0
        for req in group:
            outs = [o[start:start + req.rows] if transformed
                    else o for o in out_arrays]
            start += req.rows
            out_nd = [_ndmod._wrap(o, ctx, flavor) for o in outs]
            req.result = _gb._rebuild_output(out_struct[0], out_nd)
            req.t_done = time.monotonic()
            req.event.set()

    def _build_jit(self, in_struct, ctx, flavor):
        from .gluon import block as _gb

        params = OrderedDict(
            (n, p) for n, p in self._net.collect_params().items()
            if p._data is not None)
        names = list(params)
        raw_fn, out_struct, mutated_names = _gb._stage_fn(
            self._net.forward, params, names, in_struct,
            False, ctx, flavor)

        def serve_fn(input_arrays, param_arrays, rng_key):
            _pstore.count_trace("serving")
            return raw_fn(param_arrays, input_arrays, rng_key)

        return (jax.jit(serve_fn), names, params, out_struct, mutated_names)

    # -- ahead-of-time warmup ----------------------------------------------
    def warmup(self, *args, max_rows: Optional[int] = None) -> int:
        """Compile the declared bucket grid at deploy time, OFF the
        request path (ROADMAP item 4: on chip a serving program costs
        26–98 s of XLA compile, multiplied by the bucket grid — paid at
        deploy, not under the first user's request).

        ``args`` is ONE example request (NDArray/numpy leaves, leading
        batch axis; row count irrelevant) giving the input structure and
        per-row shapes/dtypes.  One program per bucket of the
        ``MXNET_SHAPE_BUCKETS`` grid is traced and XLA-compiled from
        abstract shapes through the ProgramStore — the exact signature,
        build, and dispatch path a real coalesced batch of that bucket
        takes, so steady state HITS these programs; with
        ``MXNET_PROGRAM_CACHE_DIR`` set they persist for the next
        process.  For the ``pow2`` policy the grid spans 1..`max_rows``
        (default ``MXNET_SERVE_MAX_BATCH``); an explicit grid is
        compiled verbatim; ``none`` compiles the example's exact shape.
        First-dispatch verification (``MXNET_SERVE_VERIFY``) still runs
        on the first real padded batch — warm-up never weakens the
        refuse-on-mismatch contract.  Returns the number of programs
        compiled (0 = grid already warm)."""
        from .gluon import block as _gb
        from .ndarray import ndarray as _ndmod

        if self._closed:
            raise RuntimeError("ServingEngine is closed")
        args = _stage_host(args)
        self._ensure_initialized(args)
        leaves, struct = _gb._flatten_args(args)
        if not leaves or any(len(l.shape) < 1 for l in leaves):
            raise ValueError(
                "warmup() needs one example request: array arguments "
                "with a leading batch axis")
        if not self._policy.enabled:
            grid = [int(leaves[0].shape[0])]
        elif self._policy.buckets() is not None:
            grid = list(self._policy.buckets())
        else:                                     # pow2
            cap = int(max_rows if max_rows is not None
                      else self._max_batch)
            grid, b = [], 1
            while b <= cap:
                grid.append(b)
                b <<= 1
        ctx = (args[0].ctx if args and hasattr(args[0], "ctx")
               else current_context())
        flavor = _ndmod._flavor_of([a for a in args
                                    if hasattr(a, "_data")])
        skey = _struct_key_of(struct)
        if self._mesh is not None:
            from .parallel import spmd as _spmd

            rep = _spmd.replicated(self._mesh)
            for p in self._net.collect_params().values():
                if p._data is None:
                    continue
                d = p._data[0]
                new = _spmd.ensure_placed(d._data, rep)
                if new is not d._data:
                    d._set_data(new)
            bsh = _spmd.batch_sharding(self._mesh)
            n_dev = int(self._mesh.devices.size)
        compiled = 0
        for b in sorted(set(int(g) for g in grid)):
            specs = [jax.ShapeDtypeStruct((b,) + tuple(l.shape[1:]),
                                          l._data.dtype) for l in leaves]
            sig = (skey,
                   tuple((tuple(s.shape), str(s.dtype)) for s in specs),
                   _ndmod._amp_generation, ctx, flavor)
            if sig in self._programs:             # already warm
                continue
            self._programs.lookup(sig)            # counted miss
            jitted, names, params, out_struct, mutated_names = \
                self._build_jit(struct, ctx, flavor)
            if self._mesh is not None:
                # shard the abstract batch like put_batch shards the
                # real one (indivisible rows replicate)
                specs = [jax.ShapeDtypeStruct(
                    s.shape, s.dtype,
                    sharding=bsh if s.shape[0] % n_dev == 0 else rep)
                    for s in specs]
            param_arrays = [params[n]._data[0]._data for n in names]
            rec = _pstore.build(
                "serving", jitted,
                (specs, param_arrays, jax.random.PRNGKey(0)),
                meta=(names, params, out_struct, mutated_names),
                label=f"{type(self._net).__name__}[warmup b={b}]")
            self._programs.insert(sig, rec)
            compiled += 1
        self._stats.inc("warmup_programs", compiled)
        return compiled

    # -- verify-or-refuse ---------------------------------------------------
    def _verify_group(self, group, out_arrays, padded_n):
        """One-time per padded signature: each request's sliced rows are
        compared against ITS OWN unpadded eager forward.  Bit-exact
        passes outright.  A last-ulp difference within fp32 kernel-
        rounding tolerance is ACCEPTED under the default verify level
        (XLA picks different gemm micro-kernels per batch extent, so
        padding a row-independent model can shift the final ulp — same
        compiled-vs-eager property as hybridize; counted as
        ``verify_ulp_accepts``), and REFUSED under strict
        ``MXNET_SERVE_VERIFY=2``.  A model whose outputs depend on the
        padded length (mean over the length axis, cross-request
        coupling, length-shaped outputs) lands orders of magnitude
        outside that tolerance and always refuses — explicitly, with
        the reason kept."""
        from .gluon import block as _gb

        strict = int(_config.get("MXNET_SERVE_VERIFY")) >= 2
        self._stats.inc("verify_runs")
        start = 0
        ulp_only = False
        for req in group:
            ref = self._eager_forward(req.args)
            ref_leaves, _ = _gb._flatten_output(ref)
            got = [onp.asarray(o[start:start + req.rows])
                   for o in out_arrays]
            start += req.rows
            if len(ref_leaves) != len(got):
                raise _BucketRefused(
                    f"padded forward returned {len(got)} outputs, eager "
                    f"returned {len(ref_leaves)}")
            for gi, (g, r) in enumerate(zip(got, ref_leaves)):
                rn = r.asnumpy()
                if g.shape != rn.shape:
                    raise _BucketRefused(
                        f"output {gi} shape follows the padded length "
                        f"(padded {g.shape} vs eager {rn.shape}) — "
                        "cannot slice back; serve with exact shapes")
                if onp.array_equal(g, rn):
                    continue
                if strict or not onp.allclose(g, rn, rtol=1e-5,
                                              atol=1e-6):
                    raise _BucketRefused(
                        f"output {gi} not bit-exact after pad+slice — "
                        "mean-style reductions over a padded axis need "
                        "masking; serve this model with exact shapes "
                        "(or MXNET_SERVE_VERIFY=1 if this was only "
                        "kernel rounding)")
                ulp_only = True
        if ulp_only:
            self._stats.inc("verify_ulp_accepts")
            _faults.record_event("serving.infer", "verify_ulp_accept")

    def _eager_forward(self, args):
        """The unpadded reference: plain eager ops (hybridize bypassed),
        inference mode.  Under a mesh the request args stage replicated
        first — eager ops require operands colocated, and the parameters
        already live replicated across the mesh."""
        if self._mesh is not None:
            from .parallel import spmd as _spmd

            rep = _spmd.replicated(self._mesh)

            def _rep(a):
                if isinstance(a, (tuple, list)):
                    return type(a)(_rep(v) for v in a)
                if hasattr(a, "_data"):
                    from .ndarray.ndarray import _wrap as _ndw

                    return _ndw(jax.device_put(a._data, rep), a.ctx, type(a))
                return a
            args = tuple(_rep(a) for a in args)
        with autograd.pause():
            return self._net.forward(*args)

    def _deliver_fallback(self, group, cause: BaseException):
        """Single-request fallback: each request re-runs alone through
        the eager forward.  A refusal reason sticks; a request that
        still fails gets THAT error delivered (never dropped)."""
        if isinstance(cause, _BucketRefused):
            self.bucket_refused = str(cause)
            # padded programs are untrustworthy for this model
            self._programs.clear()
            _faults.record_event("serving.infer", "bucket_refused",
                                 reason=str(cause))
        for req in group:
            try:
                req.result = self._eager_forward(req.args)
            except BaseException as e:
                req.error = e
            req.t_done = time.monotonic()
            req.event.set()


class _BucketRefused(RuntimeError):
    """Padding/coalescing declared unsafe for this model (sticky)."""


def _struct_key_of(struct):
    from .gluon import block as _gb

    return _gb._struct_key(struct)


def _stage_host(x):
    """numpy leaves -> device NDArrays (the DataLoader ``_wrap`` HBM
    staging applied to request payloads); NDArrays pass through."""
    from .ndarray import NDArray, array

    if isinstance(x, onp.ndarray):
        return array(x)
    if isinstance(x, (list, tuple)):
        return type(x)(_stage_host(v) for v in x)
    if isinstance(x, dict):
        return {k: _stage_host(v) for k, v in x.items()}
    return x
