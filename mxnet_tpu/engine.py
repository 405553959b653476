"""Async pipeline engine.

The reference's scheduling heart is the threaded dependency engine
(``src/engine/threaded_engine.cc``): every op is pushed with read/write
var lists and IO prefetch, host<->device copies, compute, and checkpoint
writes all overlap.  On TPU, XLA's async dispatch stream already orders
*device* work — but PRs 1-4 shrank the device side to one donated
program per step, so the step gap is now pure HOST time: the blocking
``device_put`` per batch, the AMP all-finite host read, per-batch metric
scalar reads, and stop-the-world checkpoint snapshots.  This module owns
the host side of the pipeline:

- :class:`DevicePrefetcher` / :func:`prefetch` — a depth-k transfer
  stage: a thread stages batch N+1 (device_put, optional bucket padding)
  while step N runs, preserving order, retrying transient transfer
  faults under the ``engine.prefetch`` site.
- a **drainable registry** — deferred AMP flag reads
  (``cached_step.TrainStep``), device metric accumulators (``metric``),
  async checkpoint writers (``parallel.elastic.CheckpointManager``) and
  serving queues register themselves; :func:`waitall` drains them all
  before the XLA effects barrier, giving waitall the reference semantics
  ("block until every pushed async op completed") instead of being a
  device-only fence.
- :func:`bulk` — real bulking semantics under ``NaiveEngine``: inside a
  ``bulk(n)`` scope the per-op synchronous barrier fires every n ops
  instead of every op (the reference's op-bulking knob).

``MXNET_ENGINE_TYPE=NaiveEngine`` is the debug/parity escape hatch: it
forces prefetch depth 0, a synchronous AMP gate, host-side metric
accumulation, and synchronous checkpoint snapshots — fully synchronous
execution, mirroring the reference's NaiveEngine role.
"""
from __future__ import annotations

import contextlib
import queue as _queue
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, Iterable, Iterator, Optional

from . import telemetry as _telemetry

__all__ = ["bulk", "set_bulk_size", "waitall", "engine_type", "is_naive",
           "prefetch", "DevicePrefetcher", "prefetch_depth",
           "register_drainable", "drainable_count", "naive_sync"]

_bulk_size = 15  # reference default MXNET_ENGINE_BULK_SIZE-ish
_TL = threading.local()

# Everything with outstanding async host-side state registers here; an
# object only needs a .drain() method.  WeakSet: a dropped prefetcher /
# metric / checkpoint manager unregisters itself by dying.
_DRAINABLES: "weakref.WeakSet" = weakref.WeakSet()


def engine_type() -> str:
    """Engine selection (reference CreateEngine, src/engine/engine.cc:32,
    driven by MXNET_ENGINE_TYPE).  ThreadedEnginePerDevice = the async
    pipeline over XLA dispatch (default); NaiveEngine = synchronous eager
    dispatch for deterministic debugging, same role as the reference's
    NaiveEngine.  The knob is declared uncached so flipping it
    mid-process (its whole point when debugging) takes effect on the
    next op."""
    from . import config

    return config.get("MXNET_ENGINE_TYPE")


def is_naive() -> bool:
    """Hot-path check (called per eager op by ndarray.invoke).  Goes
    through the config registry like every other env read (graftlint
    env-discipline): the knob is declared uncached, so this is one
    registry hit + one environment read — flipping it mid-process (its
    debugging role) still takes effect on the next op."""
    from . import config

    return config.get("MXNET_ENGINE_TYPE") == "NaiveEngine"


def prefetch_depth() -> int:
    """Effective device-prefetch depth (MXNET_ENGINE_PREFETCH);
    NaiveEngine forces 0 — the fully synchronous escape hatch."""
    if is_naive():
        return 0
    from . import config

    return max(0, config.get("MXNET_ENGINE_PREFETCH"))


def amp_lag() -> int:
    """Effective deferred-AMP-gate lag window (MXNET_AMP_LAG, clamped to
    one unread flag); NaiveEngine forces 0 (synchronous gate)."""
    if is_naive():
        return 0
    from . import config

    return min(1, max(0, config.get("MXNET_AMP_LAG")))


# ---------------------------------------------------------------------------
# drainable registry + waitall
# ---------------------------------------------------------------------------

def register_drainable(obj):
    """Register an object carrying outstanding async host-side state
    (must expose ``.drain()``); :func:`waitall` drains every registered
    live object.  Weakly referenced — no unregister needed."""
    _DRAINABLES.add(obj)
    return obj


def drainable_count() -> int:
    """Live drainable registrations (exported as the computed telemetry
    gauge ``engine.drainables``)."""
    return len(_DRAINABLES)


def _register_drainables_gauge():
    from . import telemetry

    telemetry.gauge_fn(
        "engine.drainables", lambda: len(_DRAINABLES),
        "live drainable registrations (prefetchers, metric "
        "accumulators, checkpoint writers, serving queues)")


_register_drainables_gauge()


def waitall():
    """Block until ALL outstanding async work completes (reference
    MXEngineWaitAll): deferred AMP flag reads, device metric
    accumulators, prefetch transfers, queued checkpoint snapshots/writes,
    serving queues — then the XLA effects barrier.  Errors a drainable
    absorbed asynchronously (e.g. a failed background checkpoint)
    surface here, exactly like the reference engine re-raising a
    captured op exception at the wait point."""
    for obj in list(_DRAINABLES):
        drain = getattr(obj, "drain", None)
        if drain is not None:
            drain()
    from .ndarray import waitall as _w

    _w()
    # a drained process has no telemetry left in flight either: flush
    # the flight recorder (no-op unless MXNET_TELEMETRY_DIR is set)
    from . import telemetry

    try:
        telemetry.flush()
    except OSError:           # unwritable dir must not fail waitall
        pass


# ---------------------------------------------------------------------------
# bulk scope (real semantics under NaiveEngine)
# ---------------------------------------------------------------------------

def set_bulk_size(size: int) -> int:
    """Reference MXEngineSetBulkSize.  The async engine fuses via XLA
    anyway; under NaiveEngine the value is the per-op sync stride inside
    a bulk scope."""
    global _bulk_size
    prev = _bulk_size
    _bulk_size = int(size)
    return prev


@contextlib.contextmanager
def bulk(size: int):
    """Reference engine bulk scope.  Under the async engine this is
    advisory (XLA already bulks); under NaiveEngine ops inside the scope
    synchronize every ``size`` ops instead of every op, and the scope
    exit is a barrier."""
    prev = set_bulk_size(size)
    _TL.bulk_depth = getattr(_TL, "bulk_depth", 0) + 1
    try:
        yield
    finally:
        _TL.bulk_depth -= 1
        tail = getattr(_TL, "bulk_tail", None)
        _TL.bulk_tail = None
        _TL.bulk_pending = 0
        if tail is not None and is_naive():
            import jax

            # graftlint: disable=host-sync -- bulk-scope exit barrier under
            # NaiveEngine: synchronous execution is the escape hatch's job
            jax.block_until_ready(tail)
        set_bulk_size(prev)


def naive_sync(arrays) -> None:
    """NaiveEngine per-op barrier (called by ndarray.invoke after each
    eager dispatch): block so errors surface at the faulting op — except
    inside a :func:`bulk` scope, where the barrier fires every
    ``bulk_size`` ops (the scope exit still syncs the tail)."""
    import jax

    if getattr(_TL, "bulk_depth", 0) <= 0 or _bulk_size <= 1:
        # graftlint: disable=host-sync -- the NaiveEngine per-op barrier
        # IS the documented synchronous mode
        jax.block_until_ready(arrays)
        return
    _TL.bulk_pending = getattr(_TL, "bulk_pending", 0) + 1
    _TL.bulk_tail = arrays
    if _TL.bulk_pending >= _bulk_size:
        _TL.bulk_pending = 0
        _TL.bulk_tail = None
        # graftlint: disable=host-sync -- same barrier, bulk stride hit
        jax.block_until_ready(arrays)


# ---------------------------------------------------------------------------
# device prefetch stage
# ---------------------------------------------------------------------------

# takes remembered for stats()' median: the gauge of a steady state, not
# a history
_AHEAD_WINDOW = 1024


def _host_bytes(item) -> int:
    """Bytes of a host batch's array leaves."""
    if isinstance(item, (tuple, list)):
        return sum(_host_bytes(x) for x in item)
    if isinstance(item, dict):
        return _host_bytes(list(item.values()))
    nbytes = getattr(item, "nbytes", None)
    if nbytes is None:
        nbytes = getattr(getattr(item, "_data", None), "nbytes", 0)  # NDArray
    return int(nbytes)


def _default_transfer(item):
    """Host batch -> device NDArrays (the DataLoader._wrap staging
    contract: one device_put per array leaf)."""
    from .ndarray import NDArray, array

    if isinstance(item, (tuple, list)):
        return type(item)(_default_transfer(x) for x in item)
    if isinstance(item, NDArray):
        return item
    return array(item)


def _bucket_pad(policy):
    """Bucket padding (PR 4's BucketPolicy grid) for host batches: the
    batch axis of every host leaf pads up to its bucket BEFORE the
    device_put, so a variable-length stream stages a bounded shape set
    (no retrace churn downstream)."""
    import numpy as onp

    def pad(x):
        if isinstance(x, (tuple, list)):
            return type(x)(pad(v) for v in x)
        # graftlint: disable=host-sync -- pads HOST batches before the
        # device_put; device arrays never reach this transfer stage
        arr = onp.asarray(x)
        if arr.ndim < 1:
            return arr
        b = policy.bucket(int(arr.shape[0]))
        if b is None or b == arr.shape[0]:
            return arr
        fill = onp.zeros((b - arr.shape[0],) + arr.shape[1:], arr.dtype)
        return onp.concatenate([arr, fill], axis=0)

    return pad


def _bucket_transfer(policy):
    pad = _bucket_pad(policy)

    def transfer(item):
        return _default_transfer(pad(item))

    return transfer


def _sharded_transfer(sharding, policy=None):
    """Device transfer that stages every batch leaf WITH the given batch
    ``NamedSharding`` (``cached_step.TrainStep.batch_sharding``): the
    prefetch thread's device_put already lands per-device shards on the
    SPMD mesh, so the compiled step pays no re-placement — and under
    multi-controller the host leaf is this process's shard of the global
    batch (``parallel.spmd.put_batch`` assembles the global array).
    Optional ``policy`` composes PR-4 bucket padding BEFORE the put."""
    from .context import current_context
    from .ndarray import NDArray
    from .ndarray.ndarray import _wrap
    from .parallel import spmd as _spmd

    mesh = sharding.mesh
    pad = _bucket_pad(policy) if policy is not None else (lambda x: x)

    def put(x):
        if isinstance(x, (tuple, list)):
            return type(x)(put(v) for v in x)
        if isinstance(x, NDArray):
            data = _spmd.put_batch(x._data, mesh)
            return x if data is x._data else _wrap(data, x.ctx, type(x))
        import numpy as onp

        # graftlint: disable=host-sync -- HOST batch leaf being staged
        return _wrap(_spmd.put_batch(onp.asarray(x), mesh),
                     current_context())

    def transfer(item):
        return put(pad(item))

    return transfer


class DevicePrefetcher:
    """Depth-k device prefetch: a transfer thread pulls items from
    ``source`` and stages them onto the device (``transfer``, default:
    the DataLoader ``_wrap`` device_put contract) into a bounded FIFO,
    so batch N+1's host->device copy overlaps step N's execution — the
    ThreadedEngine IO-prefetch stage.

    Ordering contract: one producer, one FIFO — items are delivered in
    source order, never reordered, dropped, or duplicated; a source
    exception is delivered in order, after every batch the source
    produced before it.  Transient transfer failures retry under the
    shared policy (site ``engine.prefetch``).

    ``stats()`` reports the staged count and the dispatch-ahead depth
    gauge (how many batches were already staged each time the consumer
    took one) — ``steady_ahead`` is the benchmark's headline pipeline
    metric.
    """

    def __init__(self, source: Iterable, depth: Optional[int] = None,
                 transfer: Optional[Callable] = None,
                 name: str = "prefetch"):
        self._source = iter(source)
        self._transfer = transfer or _default_transfer
        self._depth = prefetch_depth() if depth is None \
            else max(1, int(depth))
        if self._depth < 1:
            self._depth = 1
        self._q: "_queue.Queue" = _queue.Queue(maxsize=self._depth)
        self._stop = threading.Event()
        self._idle = threading.Event()  # no transfer in flight
        self._idle.set()
        self._staged = 0
        # dispatch-ahead depth at each take: the count and the maximum
        # for ever, the samples of the newest takes for the median
        self._consumed = 0
        self._max_ahead = 0
        self._ahead_samples: "deque" = deque(maxlen=_AHEAD_WINDOW)
        self._done = False
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"mxnet-{name}")
        self._thread.start()
        register_drainable(self)

    # -- producer --------------------------------------------------------
    def _put(self, item) -> None:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except _queue.Full:
                continue

    def _run(self):
        from . import faults as _faults
        from . import preemption as _preemption

        try:
            while not self._stop.is_set():
                if _preemption.draining():
                    # preemption drain: stop pulling/staging NEW batches
                    # (already-staged ones stay deliverable); the
                    # consumer sees a normal end-of-stream at the next
                    # take, so the train loop winds down cleanly
                    self._put(("end", None))
                    return
                try:
                    item = next(self._source)
                except StopIteration:
                    self._put(("end", None))
                    return
                self._idle.clear()
                t0 = time.time_ns()
                try:
                    # transfer is pure (same host batch -> same device
                    # payload), so a transient device_put hiccup retries
                    out = _faults.retry_call(self._transfer, item,
                                             site="engine.prefetch")
                finally:
                    self._idle.set()
                # the thread's own time as spans (docs/OBSERVABILITY.md,
                # "Host phases"): the transfer, then the wait for a free
                # slot of the FIFO
                t1 = time.time_ns()
                _telemetry.record_span("input.transfer", "input", t0, t1,
                                       args={"bytes": _host_bytes(item)})
                self._staged += 1
                t2 = time.time_ns()
                self._put(("ok", out))
                _telemetry.record_span("input.slot_wait", "input", t2,
                                       time.time_ns())
        except BaseException as e:   # delivered in order, then stop
            self._put(("error", e))
        finally:
            self._idle.set()

    # -- consumer --------------------------------------------------------
    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        ahead = self._q.qsize()
        kind, val = self._q.get()
        if kind == "end":
            self._done = True
            raise StopIteration
        if kind == "error":
            self._done = True
            raise val
        # only takes that yielded a batch count toward the gauge (the
        # terminal end/error take is not a consume)
        self._ahead_samples.append(ahead)
        self._consumed += 1
        self._max_ahead = max(self._max_ahead, ahead)
        return val

    # -- lifecycle / introspection --------------------------------------
    def drain(self, timeout: float = 60.0) -> None:
        """Block until the in-flight transfer (if any) has been staged —
        after drain() the device holds every batch the transfer thread
        pulled from the source."""
        self._idle.wait(timeout)

    def close(self) -> None:
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except _queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        self._done = True

    def stats(self) -> Dict[str, Any]:
        samples = list(self._ahead_samples)
        # the first take races thread start-up; steady state is the rest
        if 1 < self._consumed <= _AHEAD_WINDOW:
            samples = samples[1:]
        steady = sorted(samples)
        return {
            "depth": self._depth,
            "staged": self._staged,
            "consumed": self._consumed,
            "max_ahead": self._max_ahead,
            "steady_ahead": steady[len(steady) // 2] if steady else 0,
        }


def prefetch(source: Iterable, depth: Optional[int] = None,
             transfer: Optional[Callable] = None, bucket: bool = False,
             sharding=None):
    """Wrap an iterable of host batches in a :class:`DevicePrefetcher`.

    ``depth`` defaults to ``MXNET_ENGINE_PREFETCH``; depth 0 (or
    ``MXNET_ENGINE_TYPE=NaiveEngine``) returns a synchronous generator
    applying the same transfer inline — the escape hatch keeps the
    call-site code identical.  ``bucket=True`` pads each batch's leading
    axis up to the ``MXNET_SHAPE_BUCKETS`` grid before the device_put
    (reusing PR 4's BucketPolicy) so variable-length streams stage a
    bounded shape set.  ``sharding`` (a batch ``NamedSharding``, e.g.
    ``TrainStep.batch_sharding``) stages every leaf onto the SPMD mesh
    — batch axis sharded over ``'dp'``, per-process shard of the global
    batch under multi-controller — so sharded steps consume prefetched
    batches without a re-placement copy."""
    policy = None
    if bucket:
        from . import serving as _serving

        p = _serving.BucketPolicy()
        if p.enabled:
            policy = p
    if transfer is None:
        if sharding is not None:
            transfer = _sharded_transfer(sharding, policy)
        elif policy is not None:
            transfer = _bucket_transfer(policy)
    eff_depth = prefetch_depth() if depth is None else max(0, int(depth))
    if is_naive():
        eff_depth = 0
    fn = transfer or _default_transfer
    if eff_depth < 1:
        return (fn(item) for item in source)
    return DevicePrefetcher(source, depth=eff_depth, transfer=fn)
