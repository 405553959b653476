"""Unified telemetry: one counter registry, one event bus, one span layer.

The reference frame ships observability as a first-class subsystem
(``src/profiler/`` lock-free stat queues, engine exec stats, KVStore
server counters).  Our reproduction instead accreted ~57 ad-hoc counter
references across 10+ modules — ``cached_step.trace_count``,
``spmd.reshard_count``, ``metric.host_sync_count``,
``flash_fallback_count`` — plus
three disjoint stats surfaces (``program_store.stats()``,
``GenerativeEngine.stats()``, ``faults.events()``) and a chrome-trace
profiler the production paths never fed.  Every measured win so far
started from a counter somebody remembered to check; this module makes
those measurements ONE queryable, exportable system:

- **Counter registry** — every counter is *declared*
  (:func:`counter` with namespace-dotted name, docstring, and kind
  ``cumulative`` / ``gauge`` / ``time``) and every legacy accessor
  (``cached_step.deferred_read_count()``, ``spmd.reshard_count()``, …)
  is now a view over it.  :func:`snapshot` / :func:`delta` are cheap,
  thread-safe, and deterministically ordered (sorted by name), so two
  identical steady-state runs produce byte-identical deltas —
  ``tools/check_telemetry.py`` enforces exactly that, plus "no counter
  ships unregistered or untested".

- **Event bus** — a bounded structured log (:func:`event` /
  :func:`events`) of runtime *happenings*: retrace, fallback, shed,
  preempt, cache evict, AMP overflow, and every fault-site action
  (``faults.record_event`` mirrors here), each stamped with the current
  train-step index and a ``time.time_ns`` timestamp (the one clock of
  this module, and the clock a ``jax.profiler`` device trace counts
  from).  Capacity: ``MXNET_TELEMETRY_EVENTS``.

- **Spans** — duration records (:func:`span` context manager /
  :func:`phases` for the consecutive parts of one / :func:`record_span`
  post-hoc) unifying ``profiler.StepTimeline`` phases, the compiled
  train step and its host phases, program builds, the prefetcher's
  transfers, serving request admit→dispatch→retire lifecycles, and
  decode iterations into one chrome-trace timeline.  A record's
  ``t0_ns`` / ``t1_ns`` are ``time.time_ns`` readings taken at the
  boundary itself, so a span lies over a device trace by subtraction
  of the trace's ``profile_start_time``; every record has an ``id`` and
  the ``parent`` that caused it.  One bounded ring a category, so a
  serving burst cannot push a train step's phases out.  Completed
  spans land in the profiler's trace buffer (the existing
  ``profiler.dump`` pipe) and, under ``MXNET_TELEMETRY_XLA=1``, inside
  ``jax.profiler`` device traces via trace annotations.

- **Trace context** (ISSUE 15) — every serving request mints a
  ``trace_id`` at its admission edge (:class:`trace_scope`;
  ``MXNET_TELEMETRY_TRACE``, default on) carried in a thread-local
  stack that the replica router's dispatch/hedge threads and the decode
  scheduler re-enter, so the ``shed`` / ``failover`` / ``hedge`` /
  ``breaker`` / ``fault`` events and the ``serving`` / ``decode`` spans
  of ONE request all stamp the same id (+ parent span id).
  :func:`trace` returns the stitched lifecycle (admission → each
  dispatch attempt → prefill/decode iterations → retire/shed), and the
  chrome-trace export links the spans of one request into one flow.
  Disabled (``MXNET_TELEMETRY_TRACE=0``): no ids are minted, no trace
  fields appear anywhere, and the hot paths pay one thread-local read.

- **Exporters** — :func:`flush` writes this process's events, spans,
  and a counter snapshot as ONE atomic JSON-lines shard
  (``telemetry-r<rank>-p<pid>.jsonl``, write-then-rename so a SIGKILL
  never leaves a torn shard) under ``MXNET_TELEMETRY_DIR`` (the flight
  recorder; ``engine.waitall()`` and the preemption drain flush; the
  directory is bounded by ``MXNET_TELEMETRY_MAX_MB`` with oldest-shard
  rotation).  :func:`merge` folds a directory of per-process shards
  into one fleet snapshot (cumulative counters summed, gauges kept
  per-process) and :func:`merge_chrome_trace` into one chrome trace
  with per-process lanes.  :func:`report` renders the one-call counter
  table, bench.py stamps :func:`delta` per lane, and
  ``python -m mxnet_tpu.telemetry`` is the on-box CLI
  (``report`` / ``trace <id>`` / ``merge <dir>``).

See docs/OBSERVABILITY.md for the namespace map, event taxonomy, span
hierarchy, trace-field schema, and how to add a counter.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
import zlib
from collections import deque
from collections.abc import Mapping
from typing import Any, Callable, Dict, Iterator, List, Optional

from . import config as _config

__all__ = [
    "Counter", "CounterGroup", "counter", "gauge", "gauge_fn", "get",
    "registered", "snapshot", "delta", "reset", "instance_name",
    "event", "events", "set_step", "current_step", "next_step",
    "span", "phases", "record_span", "spans", "monotonic_offset_ns",
    "report", "flush",
    "flight_recorder_path", "KINDS",
    "tracing_enabled", "new_trace_id", "trace_scope", "current_trace",
    "current_span_id", "trace", "merge", "merge_chrome_trace", "main",
]

# one lock guards registry structure AND every counter value: increments
# are atomic, and a snapshot taken under it can never observe a torn
# multi-counter update in progress (tools/check_telemetry.py's
# thread-safety contract)
_LOCK = threading.RLock()

KINDS = ("cumulative", "gauge", "time")


class Counter:
    """One declared counter.  ``cumulative`` counters move by
    :meth:`inc` and are monotonic between resets; ``gauge`` / ``time``
    counters take :meth:`set` (and are excluded from the deterministic
    steady-state comparison the CI gate runs)."""

    __slots__ = ("name", "doc", "kind", "family", "_value")

    def __init__(self, name: str, doc: str = "", kind: str = "cumulative",
                 family: Optional[str] = None):
        if kind not in KINDS:
            raise ValueError(f"counter kind {kind!r} not in {KINDS}")
        self.name = name
        self.doc = doc
        self.kind = kind
        self.family = family
        self._value = 0.0 if kind == "time" else 0

    def inc(self, n: int = 1) -> None:
        with _LOCK:
            self._value += n

    add = inc

    def set(self, v) -> None:
        with _LOCK:
            self._value = v

    @property
    def value(self):
        with _LOCK:
            return self._value

    def reset(self) -> None:
        self.set(0.0 if self.kind == "time" else 0)

    def __int__(self) -> int:
        return int(self.value)

    def __repr__(self) -> str:
        return (f"Counter({self.name!r}, kind={self.kind!r}, "
                f"value={self.value!r})")


_COUNTERS: Dict[str, Counter] = {}
_GAUGE_FNS: Dict[str, Callable[[], Any]] = {}
_GAUGE_DOCS: Dict[str, str] = {}
_GAUGE_FAMILIES: Dict[str, Optional[str]] = {}
_SEQ: Dict[str, int] = {}


def counter(name: str, doc: str = "", kind: str = "cumulative",
            family: Optional[str] = None) -> Counter:
    """Declare (idempotently) and return the registry counter ``name``.

    Names are namespace-dotted (``cached_step.deferred_read``,
    ``program_store.train_step.traces``); dynamic per-instance counters
    (fault sites, serving engines) pass ``family`` — the stable name the
    CI gate's test-coverage check keys on."""
    with _LOCK:
        c = _COUNTERS.get(name)
        if c is None:
            c = _COUNTERS[name] = Counter(name, doc, kind, family)
        return c


def gauge(name: str, doc: str = "",
          family: Optional[str] = None) -> Counter:
    """Declare a ``gauge``-kind counter (absolute value, :meth:`set`)."""
    return counter(name, doc, kind="gauge", family=family)


def gauge_fn(name: str, fn: Callable[[], Any], doc: str = "",
             family: Optional[str] = None) -> None:
    """Register a *computed* gauge: ``snapshot()`` calls ``fn()`` for its
    value (e.g. ``engine.drainables`` = live drainable registrations).
    Per-instance gauges pass ``family`` — the stable name the CI gate's
    test-coverage check keys on, same as :func:`counter`."""
    with _LOCK:
        _GAUGE_FNS[name] = fn
        _GAUGE_DOCS[name] = doc
        _GAUGE_FAMILIES[name] = family


def register_load_gauges(engine, prefix: str) -> None:
    """Expose an engine's live ``load()`` fields — queue depth,
    in-flight occupancy, KV page-pool pressure — as computed gauges
    under its counter-group prefix (``decode.engine0.queue_depth``
    …), so the replica router's balancer, the fleet autoscaler,
    dashboards, and ``check_perf_delta`` all read the SAME numbers
    (ISSUE 17).  Weakly bound: a closed or collected engine reads 0.0
    at snapshot time instead of pinning the instance alive."""
    import weakref

    ref = weakref.ref(engine)
    # the family is the instance-stripped prefix ('decode.engine0' ->
    # 'decode.engine'), matching the engines' CounterGroup family
    fam = prefix.rstrip("0123456789")

    def _field(key: str):
        def read() -> float:
            eng = ref()
            if eng is None or getattr(eng, "_closed", False):
                return 0.0
            try:
                return float(eng.load().get(key, 0.0))
            except Exception:
                return 0.0
        return read

    for key, doc in (
            ("queue_depth", "Admitted-but-unscheduled requests on this "
             "engine (live load() view; the balancer/autoscaler "
             "input)"),
            ("in_flight", "In-flight occupancy of this engine "
             "(live rows / max rows, or staged batches; load() view)"),
            ("pool_pressure", "KV page-pool pressure of this engine "
             "(1 - free/total pages; 0 for engines without a pool)")):
        gauge_fn(f"{prefix}.{key}", _field(key), doc=doc, family=fam)


def get(name: str) -> Counter:
    with _LOCK:
        try:
            return _COUNTERS[name]
        except KeyError:
            raise KeyError(
                f"undeclared telemetry counter {name!r}; declare it with "
                "telemetry.counter(name, doc, kind)") from None


def registered() -> Dict[str, Dict[str, Any]]:
    """Metadata of every declared counter (incl. computed gauges)."""
    with _LOCK:
        out = {n: {"kind": c.kind, "doc": c.doc, "family": c.family}
               for n, c in _COUNTERS.items()}
        for n in _GAUGE_FNS:
            out.setdefault(n, {"kind": "gauge", "doc": _GAUGE_DOCS[n],
                               "family": _GAUGE_FAMILIES.get(n)})
    return out


def instance_name(prefix: str) -> str:
    """Deterministic per-process instance prefix (``serving.engine0``,
    ``serving.engine1``, …) for counter groups owned by object
    instances."""
    with _LOCK:
        n = _SEQ.get(prefix, 0)
        _SEQ[prefix] = n + 1
    return f"{prefix}{n}"


def snapshot() -> Dict[str, Any]:
    """All counter values, deterministically ordered (sorted by name).
    Cheap: one lock hold + one dict copy; computed gauges evaluate
    outside the lock (they must not re-enter the registry)."""
    with _LOCK:
        vals = {n: c._value for n, c in _COUNTERS.items()}
        fns = list(_GAUGE_FNS.items())
    for n, fn in fns:
        if n not in vals:
            try:
                vals[n] = fn()
            except Exception:
                vals[n] = None
    return dict(sorted(vals.items()))


def delta(base: Mapping, current: Optional[Mapping] = None
          ) -> Dict[str, Any]:
    """Counter movement since ``base`` (a prior :func:`snapshot`):
    cumulative/time counters subtract, gauges report their current
    value.  Counters born after ``base`` delta from 0.  Ordering is
    deterministic (sorted)."""
    cur = snapshot() if current is None else current
    kinds = registered()
    out: Dict[str, Any] = {}
    for name in sorted(cur):
        kind = kinds.get(name, {}).get("kind", "cumulative")
        v = cur[name]
        if kind == "gauge" or v is None:
            out[name] = v
            continue
        b = base.get(name, 0) or 0
        out[name] = v - b
    return out


def reset(prefix: Optional[str] = None) -> None:
    """Zero declared counters (tests/benchmarks) — all of them, or only
    those whose name starts with ``prefix``.  Events and spans are
    untouched (clear those via their own buffers)."""
    with _LOCK:
        for n, c in _COUNTERS.items():
            if prefix is None or n.startswith(prefix):
                c._value = 0.0 if c.kind == "time" else 0


class CounterGroup(Mapping):
    """A fixed-key set of registry counters under one dotted prefix —
    the per-instance ``_stats`` dicts of ``ServingEngine`` /
    ``GenerativeEngine`` / ``PagePool`` and the per-site fault counters,
    kept dict-compatible (``dict(group)`` / ``group["k"]`` / iteration)
    so every existing ``stats()`` caller and test sees plain ints, while
    the values live in the registry and ride :func:`snapshot`.

    ``group.inc(k)`` is the atomic increment; ``group[k] = v`` sets
    (``+=`` works but is get-then-set — use :meth:`inc` on paths that
    race)."""

    __slots__ = ("prefix", "_counters")

    def __init__(self, prefix: str, keys, doc: str = "",
                 kind: str = "cumulative", family: Optional[str] = None):
        self.prefix = prefix
        self._counters = {k: counter(f"{prefix}.{k}", doc, kind, family)
                          for k in keys}

    def __getitem__(self, k):
        return self._counters[k].value

    def __setitem__(self, k, v) -> None:
        self._counters[k].set(v)

    def inc(self, k, n: int = 1) -> None:
        self._counters[k].inc(n)

    def __iter__(self) -> Iterator:
        return iter(self._counters)

    def __len__(self) -> int:
        return len(self._counters)

    def reset(self) -> None:
        for c in self._counters.values():
            c.reset()


# ---------------------------------------------------------------------------
# step index (stamped onto events; advanced by cached_step.TrainStep)
# ---------------------------------------------------------------------------
_STEP: List[Optional[int]] = [None]


def set_step(i: Optional[int]) -> None:
    """Pin the current train-step index (events stamp it)."""
    _STEP[0] = i


def next_step() -> int:
    """Advance and return the process-wide step index (TrainStep calls
    this once per step; serving/decode events inherit whatever step the
    co-resident trainer is on, or None when nothing trains)."""
    with _LOCK:
        _STEP[0] = 0 if _STEP[0] is None else _STEP[0] + 1
        return _STEP[0]


def current_step() -> Optional[int]:
    return _STEP[0]


# ---------------------------------------------------------------------------
# trace context (ISSUE 15: end-to-end request identity)
# ---------------------------------------------------------------------------
# One thread-local stack of (trace_id, span_id) frames.  The OUTERMOST
# frame is minted at a request's admission edge (router.infer/generate,
# bare ServingEngine.infer / GenerativeEngine.generate); worker threads
# re-enter with the explicit id stamped on the request object, so every
# event and span a request touches — on any thread — carries one id.
class _Ambient(threading.local):
    # class-level defaults: a thread that never entered a scope reads
    # them at attribute speed (a missing thread-local attribute costs an
    # exception inside getattr)
    stack = None      # the request's frames (trace_scope)
    cur = None        # id of the innermost span open on this thread


_TRACE = _Ambient()

_TRACES_MINTED = counter(
    "telemetry.traces_minted",
    "request trace ids minted at serving admission edges "
    "(MXNET_TELEMETRY_TRACE; one id = one end-to-end request lifecycle)")


def tracing_enabled() -> bool:
    """Is request-trace minting on?  (``MXNET_TELEMETRY_TRACE``,
    default 1.)  Only admission edges consult this; everything inside a
    request reads the thread-local frame instead — with tracing off no
    frame ever exists, so no trace fields are stamped anywhere."""
    return bool(_config.get("MXNET_TELEMETRY_TRACE"))


def new_trace_id() -> str:
    """Mint a process-unique trace id (``<pid hex>-<seq hex>``)."""
    _TRACES_MINTED.inc()
    return f"{os.getpid():x}-{int(_TRACES_MINTED.value):x}"


def _trace_stack() -> List:
    st = _TRACE.stack
    if st is None:
        st = _TRACE.stack = []
    return st


def current_trace() -> Optional[str]:
    """The ambient trace id on this thread, or None (one thread-local
    read — hot-path safe)."""
    st = _TRACE.stack
    return st[-1][0] if st else None


def current_span_id() -> Optional[str]:
    """The ambient parent-span id on this thread, or None."""
    st = _TRACE.stack
    return st[-1][1] if st else None


def _next_span_id() -> str:
    # itertools.count: one atomic step, no lock on the step path
    return f"s{next(_SPAN_IDS):x}"


def _ambient_parent() -> Optional[str]:
    """The span a record made now on this thread was caused by: the
    request's frame inside a :class:`trace_scope`, else the innermost
    :class:`span` / :class:`phases` part open on this thread."""
    st = _TRACE.stack
    if st and st[-1][1] is not None:
        return st[-1][1]
    return _TRACE.cur


class trace_scope:
    """Establish (or re-enter) the thread's request-trace context.

    - ``trace_scope()`` at an admission edge: inherit the ambient trace
      when one exists (a routed request re-entering an engine), else
      mint a fresh id when :func:`tracing_enabled` — else a no-op.
    - ``trace_scope(trace_id=req.trace_id, parent=req.span_id)`` on a
      worker thread: carry the request's ONE identity across the thread
      hop (the deadline-budget ``until=`` idiom, applied to identity).
      A ``None`` id is a no-op passthrough, so disabled-mode requests
      stay zero-overhead on every thread they touch.

    ``scope.trace_id`` is the active id (None when the scope is a
    passthrough)."""

    __slots__ = ("trace_id", "_parent", "_pushed", "_explicit")

    _UNSET = object()

    def __init__(self, trace_id: Any = _UNSET,
                 parent: Optional[str] = None):
        self._explicit = trace_id is not trace_scope._UNSET
        self.trace_id = (None if trace_id is trace_scope._UNSET
                         else trace_id)
        self._parent = parent
        self._pushed = False

    def __enter__(self) -> "trace_scope":
        tid = self.trace_id
        if tid is None and not self._explicit:
            tid = current_trace()
            if tid is None and tracing_enabled():
                tid = new_trace_id()
        if tid is None:
            return self
        st = _trace_stack()
        parent = self._parent
        if parent is None and st:
            parent = st[-1][1]
        st.append((tid, parent))
        self.trace_id = tid
        self._pushed = True
        return self

    def __exit__(self, *exc) -> None:
        if self._pushed:
            _trace_stack().pop()
            self._pushed = False


def trace(trace_id: str) -> Dict[str, Any]:
    """The stitched lifecycle of one request: every buffered event
    stamped with ``trace_id`` plus every span that carries it (directly,
    or in its ``args.trace_ids`` list — decode iterations batch many
    requests into one dispatch), merged into one time-ordered
    ``records`` list.  Events and spans share one clock
    (``time.time_ns``), so admission → dispatch attempts →
    prefill/decode iterations → retire/shed come back in lifecycle
    order."""
    evs = [e for e in events() if e.get("trace_id") == trace_id]
    sps = []
    for s in spans():
        if s.get("trace_id") == trace_id or \
                trace_id in ((s.get("args") or {}).get("trace_ids") or ()):
            sps.append(s)
    records: List[Dict[str, Any]] = []
    for e in evs:
        records.append(dict(e, type="event"))
    for s in sps:
        records.append(dict(s, type="span", t_us=s["t0_us"]))
    records.sort(key=lambda r: (r["t_us"], r.get("seq", 0)))
    return {"trace_id": trace_id, "events": evs, "spans": sps,
            "records": records}


# ---------------------------------------------------------------------------
# event bus
# ---------------------------------------------------------------------------
# taxonomy (docs/OBSERVABILITY.md): retrace | fallback | shed | preempt |
# cache_evict | amp_overflow | fault | <caller-defined>
_EVENTS: "deque" = deque(
    maxlen=max(1, int(_config.get("MXNET_TELEMETRY_EVENTS"))))
_EVENTS_EMITTED = counter(
    "telemetry.events", "structured events emitted through the bus "
    "(the bounded buffer keeps the newest MXNET_TELEMETRY_EVENTS)")
_EVT_LOCK = threading.Lock()
_FLUSH_SEQ = [0]          # bus sequence already flushed to disk


_RESERVED_EVENT_KEYS = ("kind", "name", "step", "t_us", "seq",
                        "trace_id", "parent")


def event(kind: str, name: str, /, step: Any = "auto", **fields) -> None:
    """Append one structured event: ``kind`` from the taxonomy, ``name``
    the subsystem/site, ``step`` the train-step index (default: the
    current one), plus a microsecond timestamp on the spans' clock
    (``time.time_ns``).  Inside a
    request's :class:`trace_scope` the event additionally stamps
    ``trace_id`` (+ ``parent`` span id) — nothing otherwise.  Extra
    fields whose names collide with the bus keys are prefixed ``x_``."""
    ev: Dict[str, Any] = {
        "kind": kind, "name": name,
        "step": current_step() if step == "auto" else step,
        "t_us": time.time_ns() // 1000,
    }
    tid = current_trace()
    if tid is not None:
        ev["trace_id"] = tid
        sid = current_span_id()
        if sid is not None:
            ev["parent"] = sid
    for k, v in fields.items():
        if v is not None:
            ev["x_" + k if k in _RESERVED_EVENT_KEYS else k] = v
    with _EVT_LOCK:
        _EVENTS_EMITTED.inc()
        ev["seq"] = int(_EVENTS_EMITTED.value)
        _EVENTS.append(ev)


_EVENT_SOURCES: List[Callable[[], None]] = []


def event_source(poll: Callable[[], None]) -> None:
    """Register ``poll``, called at the start of every :func:`events`: for
    happenings only the device knows (a count a step program accumulates),
    which become events when somebody asks, not by a host read in the step.
    ``poll`` emits what is new through :func:`event` and must not raise."""
    with _EVT_LOCK:
        if poll not in _EVENT_SOURCES:
            _EVENT_SOURCES.append(poll)


def events(kind: Optional[str] = None,
           name: Optional[str] = None) -> List[Dict[str, Any]]:
    with _EVT_LOCK:
        sources = list(_EVENT_SOURCES)
    for poll in sources:
        poll()
    with _EVT_LOCK:
        evs = list(_EVENTS)
    if kind is not None:
        evs = [e for e in evs if e["kind"] == kind]
    if name is not None:
        evs = [e for e in evs if e["name"] == name]
    return evs


def clear_events() -> None:
    """Drop buffered events (tests); the emitted counter is untouched."""
    with _EVT_LOCK:
        _EVENTS.clear()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
# One ring a category.  A record is a plain tuple (what the step path
# pays for is one append); :func:`spans` builds the dicts when somebody
# reads.  8,192 records a ring: a ``--trace 1`` run of the benchmark
# keeps its traced steps and its timed window (about 110 steps of 6
# ``train_step`` records, two ``input`` records a batch) several times
# over, and a CPU rehearsal's faster steps too; a reader that needs an
# older record than the ring holds must say so, never average what is
# left (``perfbench/host_view.py``).
_RING = 8192
_SPANS: Dict[str, "deque"] = {}
_SPAN_IDS = itertools.count(1)        # span ids
_SPAN_SEQ = itertools.count(1)        # record order + flush cursor
_SPANS_RECORDED = counter(
    "telemetry.spans", "completed spans recorded (train_step / program "
    "/ input / step_phase / serving / decode / user categories)")
# trace ids whose chrome flow already emitted its "s" (start) arrow —
# later spans of the same trace emit "t" (step) so the whole request
# renders as ONE connected flow in chrome://tracing / Perfetto
_FLOW_STARTED: set = set()
_PROFILER = None                      # mxnet_tpu.profiler, on first use


def monotonic_offset_ns() -> int:
    """What to add to a ``time.monotonic`` reading (the clock deadlines
    are kept on) to put it on the spans' clock.  Read where it is used,
    never once at import: the wall clock is slewed."""
    return time.time_ns() - time.monotonic_ns()


def _flow_id(trace_id: str) -> int:
    return zlib.crc32(trace_id.encode()) & 0x7FFFFFFF


def _ring(cat: str) -> "deque":
    ring = _SPANS.get(cat)
    if ring is None:
        with _LOCK:
            ring = _SPANS.setdefault(cat, deque(maxlen=_RING))
    return ring


def _record(name, cat, t0_ns, t1_ns, step, args, sid, parent) -> None:
    """One completed span into its category's ring and, when collection
    runs, into the profiler's chrome-trace buffer."""
    global _PROFILER
    tid = current_trace()
    _SPANS_RECORDED.inc()
    _ring(cat).append((next(_SPAN_SEQ), name, cat, step, t0_ns, t1_ns,
                       threading.get_ident(), sid, parent, tid, args))
    if _PROFILER is None:
        from . import profiler as _PROFILER
    if not _PROFILER.collecting():
        return
    _PROFILER._emit(name, cat, "X", ts=t0_ns // 1000,
                    dur=max((t1_ns - t0_ns) // 1000, 1), args=args)
    if tid is not None:
        # one request = one chrome flow: an "s" arrow from the trace's
        # first span, "t" steps through every later one
        first = tid not in _FLOW_STARTED
        if first:
            _FLOW_STARTED.add(tid)
            if len(_FLOW_STARTED) > 8192:
                _FLOW_STARTED.clear()
                _FLOW_STARTED.add(tid)
        _PROFILER._emit(f"trace:{tid}", "flow", "s" if first else "t",
                        ts=t0_ns // 1000, flow_id=_flow_id(tid))


def _as_dict(rec) -> Dict[str, Any]:
    seq, name, cat, step, t0, t1, thread, sid, parent, tid, args = rec
    out = {"name": name, "cat": cat, "step": step,
           "t0_ns": t0, "t1_ns": t1,
           "t0_us": t0 // 1000, "dur_us": max((t1 - t0) // 1000, 1),
           "thread": thread, "id": sid, "parent": parent, "seq": seq}
    if tid is not None:
        out["trace_id"] = tid
    if args:
        out["args"] = dict(args)
    return out


def record_span(name: str, cat: str, t0_ns: int, t1_ns: int,
                step: Any = "auto",
                args: Optional[Dict[str, Any]] = None) -> None:
    """Record one completed span post-hoc (the lifecycle spans whose
    endpoints were timed elsewhere — serving admit→retire), ``t0_ns`` /
    ``t1_ns`` on ``time.time_ns``.  Also emits into the profiler's
    chrome-trace buffer when collection is running, so every span
    category lands in the one ``profiler.dump`` timeline.  The record's
    ``parent`` is the span that caused it (:func:`_ambient_parent`);
    inside a request's :class:`trace_scope` it stamps ``trace_id`` too
    and the chrome export links it into the request's flow."""
    _record(name, cat, t0_ns, t1_ns,
            current_step() if step == "auto" else step,
            dict(args) if args else None, _next_span_id(),
            _ambient_parent())


def _xla_annotations_on() -> bool:
    return bool(_config.get("MXNET_TELEMETRY_XLA"))


class span:
    """Context-manager span: times the enclosed work, records it (see
    :func:`record_span`), and — with ``MXNET_TELEMETRY_XLA=1`` — wraps
    it in a ``jax.profiler`` trace annotation so the host-side bracket
    shows up inside XLA device profiles.  It takes an id at entry and is
    the ambient PARENT of everything recorded underneath it on this
    thread (and, inside a request's :class:`trace_scope`, on the threads
    the request's frame is carried to)."""

    __slots__ = ("name", "cat", "args", "_t0", "_ann", "_sid", "_pushed",
                 "_parent", "_outer")

    def __init__(self, name: str, cat: str = "user",
                 args: Optional[Dict[str, Any]] = None):
        self.name = name
        self.cat = cat
        self.args = dict(args) if args else None
        self._t0 = None
        self._ann = None
        self._sid = None
        self._pushed = False

    def annotate(self, **kw) -> "span":
        """Attach/extend span args mid-flight (recorded at exit)."""
        if self.args is None:
            self.args = {}
        self.args.update(kw)
        return self

    def __enter__(self) -> "span":
        self._t0 = time.time_ns()
        self._sid = _next_span_id()
        self._parent = _ambient_parent()
        self._outer = _TRACE.cur
        _TRACE.cur = self._sid
        st = _TRACE.stack
        if st:
            st.append((st[-1][0], self._sid))
            self._pushed = True
        if _xla_annotations_on():
            try:
                import jax

                self._ann = jax.profiler.TraceAnnotation(
                    f"{self.cat}:{self.name}")
                self._ann.__enter__()
            except Exception:
                self._ann = None
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            finally:
                self._ann = None
        if self._pushed:
            _trace_stack().pop()
            self._pushed = False
        if self._t0 is not None:
            _TRACE.cur = self._outer
            _record(self.name, self.cat, self._t0, time.time_ns(),
                    current_step(), self.args, self._sid, self._parent)
            self._t0 = None


class phases:
    """The consecutive parts of the span open on this thread, as its
    children: ``to(name)`` ends the part that is open and starts the next
    at the SAME reading of the clock (one reading a boundary; no gap and
    no overlap between parts), ``end()`` closes the last.  While a part
    is open it is the ambient parent, so what it causes (a
    ``program.build`` under ``train_step.launch``) records it.  No
    ``TraceAnnotation``: a part costs two tuple slots and an append.
    ``drop()`` forgets the open part unrecorded (the step took another
    path)."""

    __slots__ = ("cat", "_parent", "_name", "_t0", "_sid", "_args")

    def __init__(self, cat: str):
        self.cat = cat
        self._parent = _TRACE.cur
        self._name = None

    def _close(self, now: int) -> None:
        if self._name is not None:
            _record(self._name, self.cat, self._t0, now, current_step(),
                    self._args, self._sid, self._parent)

    def to(self, name: str, **args) -> None:
        now = time.time_ns()
        self._close(now)
        self._name, self._t0, self._args = name, now, args or None
        self._sid = _TRACE.cur = _next_span_id()

    def end(self) -> None:
        self._close(time.time_ns())
        self.drop()

    def drop(self) -> None:
        self._name = None
        _TRACE.cur = self._parent


def _records(cat: Optional[str] = None) -> List[tuple]:
    """One ring's tuples, or all rings' in the order they were recorded
    (a tuple starts with its sequence number)."""
    if cat is not None:
        return list(_SPANS.get(cat, ()))
    return sorted(r for ring in list(_SPANS.values()) for r in list(ring))


def spans(cat: Optional[str] = None, limit: Optional[int] = None,
          name: Optional[str] = None) -> List[Dict[str, Any]]:
    """Recent completed span records, oldest first: of one category's
    ring, or of all rings merged in the order they were recorded;
    ``name`` keeps one span name, ``limit`` the newest so many."""
    recs = _records(cat)
    if name is not None:
        recs = [r for r in recs if r[1] == name]
    if limit is not None:
        recs = recs[-int(limit):]
    return [_as_dict(r) for r in recs]


def clear_spans() -> None:
    for ring in list(_SPANS.values()):
        ring.clear()
    _FLOW_STARTED.clear()


# ---------------------------------------------------------------------------
# exporters: the flight recorder (per-process shards) + fleet merge
# ---------------------------------------------------------------------------
_SHARDS_ROTATED = counter(
    "telemetry.shards_rotated",
    "flight-recorder shards deleted by the MXNET_TELEMETRY_MAX_MB "
    "oldest-first size-cap rotation (a week-long drill cannot fill "
    "the disk)")


def _flight_dir() -> Optional[str]:
    d = _config.get("MXNET_TELEMETRY_DIR")
    if not d:
        return None
    return os.path.expanduser(d)


def _process_rank() -> int:
    r = _config.get("MXNET_TPU_PROC_ID")
    return int(r) if r is not None else 0


def flight_recorder_path() -> Optional[str]:
    """This process's shard file (``MXNET_TELEMETRY_DIR`` set), else
    None (recorder off).  Shards are pid/rank-stamped —
    ``telemetry-r<rank>-p<pid>.jsonl`` — so every process of a drill or
    a multi-controller job writes its own file and :func:`merge` folds
    them back together."""
    d = _flight_dir()
    if d is None:
        return None
    return os.path.join(
        d, f"telemetry-r{_process_rank()}-p{os.getpid()}.jsonl")


_FLUSH_LOCK = threading.Lock()
_SPAN_FLUSH_SEQ = [0]     # span sequence already flushed to disk


def _shard_line_cap() -> int:
    # bound the per-shard record history like the in-memory bus: the
    # newest 4x the bus capacity of event+span lines survive a rewrite
    return 4 * max(1, int(_config.get("MXNET_TELEMETRY_EVENTS")))


def _rotate_shards(directory: str, keep: str) -> int:
    """Enforce ``MXNET_TELEMETRY_MAX_MB`` over the shard directory:
    delete oldest-mtime shards (never this process's own) until the
    total fits.  Returns shards removed."""
    cap_mb = float(_config.get("MXNET_TELEMETRY_MAX_MB"))
    if cap_mb <= 0:
        return 0
    cap = cap_mb * 1024 * 1024
    shards = []
    try:
        for fn in os.listdir(directory):
            if fn.startswith("telemetry-") and fn.endswith(".jsonl"):
                p = os.path.join(directory, fn)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                shards.append((st.st_mtime, st.st_size, p))
    except OSError:
        return 0
    total = sum(s for _m, s, _p in shards)
    removed = 0
    for _mtime, size, p in sorted(shards):
        if total <= cap:
            break
        if os.path.abspath(p) == os.path.abspath(keep):
            continue
        try:
            os.unlink(p)
        except OSError:
            continue
        total -= size
        removed += 1
    if removed:
        _SHARDS_ROTATED.inc(removed)
    return removed


def flush(snapshot_too: bool = True,
          path: Optional[str] = None) -> Optional[str]:
    """Flight recorder: fold every event and span not yet flushed plus
    (default) one fresh ``{"kind": "snapshot"}`` record of all counters
    into this process's shard under ``MXNET_TELEMETRY_DIR``.  The shard
    is rewritten whole via write-then-rename, so a SIGKILL mid-flush
    can never leave a torn JSON-lines file for :func:`merge` to choke
    on — the previous complete shard survives.  No-op returning None
    when the knob is unset.  ``engine.waitall()`` and the preemption
    drain call this, so a drained process always has its telemetry on
    disk.  ``path`` overrides the shard file (tests)."""
    path = flight_recorder_path() if path is None else path
    if path is None:
        return None
    with _FLUSH_LOCK:
        with _EVT_LOCK:
            pending = [e for e in _EVENTS if e["seq"] > _FLUSH_SEQ[0]]
            if pending:
                _FLUSH_SEQ[0] = pending[-1]["seq"]
        pend_spans = [_as_dict(r) for r in _records()
                      if r[0] > _SPAN_FLUSH_SEQ[0]]
        if pend_spans:
            _SPAN_FLUSH_SEQ[0] = pend_spans[-1]["seq"]
        # prior data lines survive the rewrite (meta + snapshot are
        # regenerated fresh each flush — only the newest matters)
        old: List[str] = []
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        kind = json.loads(line).get("kind")
                    except ValueError:
                        continue          # torn line from a legacy shard
                    if kind not in ("meta", "snapshot"):
                        old.append(line)
        except OSError:
            pass
        lines = old
        lines.extend(json.dumps(e) for e in pending)
        lines.extend(json.dumps({"kind": "span", **s})
                     for s in pend_spans)
        cap = _shard_line_cap()
        if len(lines) > cap:
            lines = lines[-cap:]
        meta = {"kind": "meta", "pid": os.getpid(),
                "rank": _process_rank(),
                "t_us": time.time_ns() // 1000,
                "counter_kinds": {n: m["kind"]
                                  for n, m in registered().items()}}
        directory = os.path.dirname(path)
        os.makedirs(directory, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(json.dumps(meta) + "\n")
            for line in lines:
                f.write(line + "\n")
            if snapshot_too:
                f.write(json.dumps({
                    "kind": "snapshot", "step": current_step(),
                    "t_us": time.time_ns() // 1000,
                    "counters": snapshot()}) + "\n")
        os.replace(tmp, path)
        _rotate_shards(directory, keep=path)
    return path


# -- fleet merge ------------------------------------------------------------

def _read_shard(path: str) -> Dict[str, Any]:
    sh: Dict[str, Any] = {"path": path, "meta": {}, "snapshot": None,
                          "events": [], "spans": [], "skipped_lines": 0}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                sh["skipped_lines"] += 1      # torn tail — legacy shard
                continue
            kind = rec.get("kind")
            if kind == "meta":
                sh["meta"] = rec
            elif kind == "snapshot":
                sh["snapshot"] = rec          # last one wins
            elif kind == "span":
                sh["spans"].append(rec)
            else:
                sh["events"].append(rec)
    return sh


def merge(directory: str) -> Dict[str, Any]:
    """Fold a directory of per-process flight-recorder shards into ONE
    fleet snapshot: cumulative/time counters SUM across processes,
    gauges stay per-process (summing a queue-depth gauge across ranks
    is a lie), and every event/span comes back stamped with its
    ``pid``/``rank``/``shard``.  Torn or mid-write files (``*.tmp``,
    invalid trailing lines) are skipped, never fatal — a SIGKILLed
    child costs its unflushed tail, not the merge."""
    directory = os.path.expanduser(directory)
    shards: List[Dict[str, Any]] = []
    for fn in sorted(os.listdir(directory)):
        if not (fn.startswith("telemetry-") and fn.endswith(".jsonl")):
            continue
        try:
            shards.append(_read_shard(os.path.join(directory, fn)))
        except OSError:
            continue
    counters: Dict[str, Any] = {}
    gauges: Dict[str, Dict[str, Any]] = {}
    events_all: List[Dict[str, Any]] = []
    spans_all: List[Dict[str, Any]] = []
    processes: List[Dict[str, Any]] = []
    skipped = 0
    for sh in shards:
        name = os.path.basename(sh["path"])
        meta = sh["meta"]
        pid, rank = meta.get("pid"), meta.get("rank", 0)
        kinds = meta.get("counter_kinds", {})
        processes.append({"shard": name, "pid": pid, "rank": rank,
                          "events": len(sh["events"]),
                          "spans": len(sh["spans"]),
                          "skipped_lines": sh["skipped_lines"]})
        skipped += sh["skipped_lines"]
        snap = (sh["snapshot"] or {}).get("counters", {})
        for cname, val in snap.items():
            kind = kinds.get(cname, "cumulative")
            if kind == "gauge" or val is None:
                gauges.setdefault(cname, {})[name] = val
            else:
                counters[cname] = counters.get(cname, 0) + val
        for ev in sh["events"]:
            events_all.append(dict(ev, pid=pid, rank=rank, shard=name))
        for sp in sh["spans"]:
            spans_all.append(dict(sp, pid=pid, rank=rank, shard=name))
    events_all.sort(key=lambda e: e.get("t_us", 0))
    spans_all.sort(key=lambda s: s.get("t0_us", 0))
    return {
        "dir": directory,
        "shards": [os.path.basename(s["path"]) for s in shards],
        "processes": processes,
        "counters": dict(sorted(counters.items())),
        "gauges": dict(sorted(gauges.items())),
        "events": events_all,
        "spans": spans_all,
        "skipped_lines": skipped,
    }


def merge_chrome_trace(directory: str,
                       merged: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
    """One chrome trace over every process's shard: each process gets
    its own lane (``pid`` + a ``process_name`` metadata row naming the
    rank), spans land as duration events, and spans sharing a
    ``trace_id`` link into one flow ACROSS processes — a routed request
    that crossed a drill child renders as one connected arrow chain."""
    m = merged if merged is not None else merge(directory)
    events: List[Dict[str, Any]] = []
    for proc in m["processes"]:
        pid = proc["pid"] if proc["pid"] is not None else proc["shard"]
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": f"rank {proc['rank']} "
                                        f"({proc['shard']})"}})
    flow_started: set = set()
    for sp in m["spans"]:
        pid = sp.get("pid") if sp.get("pid") is not None \
            else sp.get("shard")
        ev = {"name": sp["name"], "cat": sp["cat"], "ph": "X",
              "pid": pid, "tid": sp.get("thread", 0),
              "ts": sp["t0_us"], "dur": sp["dur_us"]}
        args = dict(sp.get("args") or {})
        if sp.get("trace_id"):
            args["trace_id"] = sp["trace_id"]
        if args:
            ev["args"] = args
        events.append(ev)
        tid = sp.get("trace_id")
        if tid:
            first = tid not in flow_started
            flow_started.add(tid)
            events.append({"name": f"trace:{tid}", "cat": "flow",
                           "ph": "s" if first else "t", "pid": pid,
                           "tid": sp.get("thread", 0), "ts": sp["t0_us"],
                           "id": _flow_id(tid)})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def report(prefix: Optional[str] = None, nonzero_only: bool = True) -> str:
    """One-call counter table (name, kind, value), grouped by top-level
    namespace — the human end of the registry."""
    snap = snapshot()
    kinds = registered()
    lines = [f"{'Counter':<52}{'Kind':>12}{'Value':>16}", "=" * 80]
    last_ns = None
    for name, val in snap.items():
        if prefix is not None and not name.startswith(prefix):
            continue
        if nonzero_only and not val:
            continue
        ns = name.split(".", 1)[0]
        if ns != last_ns:
            if last_ns is not None:
                lines.append("-" * 80)
            last_ns = ns
        kind = kinds.get(name, {}).get("kind", "?")
        if isinstance(val, float):
            lines.append(f"{name:<52}{kind:>12}{val:>16.3f}")
        else:
            lines.append(f"{name:<52}{kind:>12}{val!s:>16}")
    lines.append("=" * 80)
    lines.append(f"{len(snap)} declared counters; "
                 f"{len(events())} buffered events; "
                 f"{len(spans())} buffered spans")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CLI: python -m mxnet_tpu.telemetry {report | trace <id> | merge <dir>}
# ---------------------------------------------------------------------------

def _merged_report(merged: Dict[str, Any],
                   prefix: Optional[str] = None) -> str:
    """The :func:`report` table rendered over a fleet merge."""
    lines = [f"{'Counter (fleet sum)':<52}{'Value':>16}", "=" * 68]
    for name, val in merged["counters"].items():
        if prefix is not None and not name.startswith(prefix):
            continue
        if not val:
            continue
        if isinstance(val, float):
            lines.append(f"{name:<52}{val:>16.3f}")
        else:
            lines.append(f"{name:<52}{val!s:>16}")
    lines.append("=" * 68)
    lines.append(f"{len(merged['shards'])} shard(s): "
                 f"{', '.join(merged['shards']) or '-'}; "
                 f"{len(merged['events'])} events, "
                 f"{len(merged['spans'])} spans"
                 + (f"; {merged['skipped_lines']} torn line(s) skipped"
                    if merged["skipped_lines"] else ""))
    return "\n".join(lines)


def _trace_from_merge(merged: Dict[str, Any],
                      trace_id: str) -> Dict[str, Any]:
    """:func:`trace`, but stitched from a shard merge instead of the
    in-process buffers (the on-box inspection path)."""
    evs = [e for e in merged["events"] if e.get("trace_id") == trace_id]
    sps = [s for s in merged["spans"]
           if s.get("trace_id") == trace_id or
           trace_id in ((s.get("args") or {}).get("trace_ids") or ())]
    records = [dict(e, type="event") for e in evs]
    records += [dict(s, type="span", t_us=s["t0_us"]) for s in sps]
    records.sort(key=lambda r: (r["t_us"], r.get("seq", 0)))
    return {"trace_id": trace_id, "events": evs, "spans": sps,
            "records": records}


def main(argv: Optional[List[str]] = None) -> int:
    """On-box inspection without writing a script (OBSERVABILITY.md):

    - ``report [--dir D] [--prefix P]`` — the counter table; with
      ``--dir`` the FLEET sum over that shard directory.
    - ``trace <id> [--dir D]`` — one request's stitched lifecycle
      (events + spans in order), from the in-process buffers or a
      shard directory.
    - ``merge <dir> [--json] [--chrome OUT]`` — fold shards into one
      snapshot; ``--json`` dumps the full merge, ``--chrome`` writes
      the per-process-lane chrome trace.
    """
    import argparse

    p = argparse.ArgumentParser(prog="python -m mxnet_tpu.telemetry",
                                description=__doc__.splitlines()[0])
    sub = p.add_subparsers(dest="cmd", required=True)
    rp = sub.add_parser("report", help="counter table")
    rp.add_argument("--dir", default=None,
                    help="shard directory (default: this process)")
    rp.add_argument("--prefix", default=None)
    tp = sub.add_parser("trace", help="one request's stitched lifecycle")
    tp.add_argument("trace_id")
    tp.add_argument("--dir", default=None,
                    help="shard directory (default: in-process buffers)")
    mp = sub.add_parser("merge", help="fold shards into one snapshot")
    mp.add_argument("dir")
    mp.add_argument("--json", action="store_true",
                    help="dump the full merge as JSON")
    mp.add_argument("--chrome", default=None, metavar="OUT",
                    help="also write the merged chrome trace here")
    a = p.parse_args(argv)
    if a.cmd == "report":
        if a.dir:
            print(_merged_report(merge(a.dir), prefix=a.prefix))
        else:
            print(report(prefix=a.prefix))
        return 0
    if a.cmd == "trace":
        tr = (_trace_from_merge(merge(a.dir), a.trace_id) if a.dir
              else trace(a.trace_id))
        print(json.dumps(tr, indent=2, default=str))
        return 0 if tr["records"] else 1
    merged = merge(a.dir)
    if a.chrome:
        with open(a.chrome, "w") as f:
            json.dump(merge_chrome_trace(a.dir, merged), f)
    if a.json:
        print(json.dumps(merged, default=str))
    else:
        print(_merged_report(merged))
    return 0


if __name__ == "__main__":          # pragma: no cover - CLI entry
    import sys as _sys

    _sys.exit(main())
