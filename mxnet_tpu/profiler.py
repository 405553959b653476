"""``mx.profiler`` — tracing and profiling.

Reference analog: ``src/profiler/`` (lock-free stat queue, Chrome-trace
dump, aggregate table) + ``python/mxnet/profiler.py:34-407`` (set_config,
pause/resume, user scopes Task/Frame/Event/Counter).

TPU-native design: two layers —
1. device/XLA level: ``jax.profiler`` trace sessions (TensorBoard format)
   capture compiled-program timelines, the analog of the reference's
   engine-exec brackets;
2. python level: user scopes and op-dispatch events recorded into an
   in-process buffer and dumped as Chrome trace JSON (``dump``/``dumps``),
   byte-compatible with chrome://tracing like the reference's output.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional

__all__ = ["set_config", "set_state", "state", "pause", "resume", "dump",
           "dumps", "Task", "Frame", "Event", "Counter", "Marker", "scope",
           "StepTimeline"]

_LOCK = threading.Lock()
_CONFIG = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": True,
    "aggregate_stats": False,
    "xla_trace_dir": None,
}
_RUNNING = False
_PAUSED = False
_EVENTS: List[dict] = []
_XLA_ACTIVE = False


def set_config(**kwargs):
    """Configure the profiler (reference profiler.py set_config)."""
    for k, v in kwargs.items():
        if k in ("filename", "file_name"):
            _CONFIG["filename"] = v
        elif k in _CONFIG:
            _CONFIG[k] = v
        # unknown kwargs accepted for reference-arg parity (continuous_dump…)


def state():
    return "run" if (_RUNNING and not _PAUSED) else "stop"


def set_state(state_name="stop"):
    """'run' starts collection (+XLA trace if xla_trace_dir configured);
    'stop' ends it."""
    global _RUNNING, _XLA_ACTIVE
    if state_name == "run":
        _RUNNING = True
        with _LOCK:
            _EVENTS.clear()
        tdir = _CONFIG["xla_trace_dir"]
        if tdir and not _XLA_ACTIVE:
            import jax

            jax.profiler.start_trace(tdir)
            _XLA_ACTIVE = True
    elif state_name == "stop":
        _RUNNING = False
        if _XLA_ACTIVE:
            import jax

            jax.profiler.stop_trace()
            _XLA_ACTIVE = False
    else:
        raise ValueError("state must be 'run' or 'stop'")


def pause(profile_process="worker"):
    global _PAUSED
    _PAUSED = True


def resume(profile_process="worker"):
    global _PAUSED
    _PAUSED = False


def collecting() -> bool:
    """True while emitted events are kept (``set_state('run')``, not
    paused): what a caller with a cost of its own asks before it builds
    an event."""
    return _RUNNING and not _PAUSED


def ops_active() -> bool:
    """True when imperative op bracketing should record (the reference
    engine brackets every Push under kImperative mode,
    src/engine/threaded_engine.cc:288-295)."""
    return _RUNNING and not _PAUSED and _CONFIG["profile_imperative"]


def record_op(name: str, t0_ns: int, t1_ns: int) -> None:
    """Emit one imperative op's dispatch bracket (called by the NDArray
    invoke path; duration = host-side dispatch, the async analog of the
    reference's operator-execution stat)."""
    _emit(name, "operator", "X", ts=t0_ns // 1000,
          dur=max((t1_ns - t0_ns) // 1000, 1))


def _emit(name, cat, ph, ts=None, dur=None, args=None, flow_id=None):
    """One chrome-trace event.  ``ts`` is microseconds of
    ``time.time_ns``, the clock of ``telemetry``'s spans and of a
    ``jax.profiler`` device trace, so the three line up."""
    if not _RUNNING or _PAUSED:
        return
    ev = {"name": name, "cat": cat, "ph": ph, "pid": os.getpid(),
          "tid": threading.get_ident(),
          "ts": (time.time_ns() // 1000) if ts is None else ts}
    if dur is not None:
        ev["dur"] = dur
    if args is not None:
        ev["args"] = args
    if flow_id is not None:
        # chrome flow events ("s"/"t"/"f") chain on a shared id — the
        # telemetry span layer links one request's spans into one flow
        ev["id"] = flow_id
    with _LOCK:
        _EVENTS.append(ev)


def dumps(reset=False, format="table") -> str:
    """Aggregate stats of recorded durations (reference DumpAggregate);
    ``format`` is 'table' or 'json'.

    ``reset=True`` clears the trace-event buffer ONLY.  Declared
    counters (``profiler.Counter`` → the ``profiler.*`` telemetry
    registry entries) keep their values: a reset drops recorded events,
    never registered state (tests/test_telemetry.py pins this)."""
    if format not in ("table", "json"):  # validate before touching events
        raise ValueError("format must be 'table' or 'json'")
    with _LOCK:
        events = list(_EVENTS)
        if reset:
            _EVENTS.clear()
    if format == "json":
        return json.dumps({"traceEvents": events, "displayTimeUnit": "ms"})
    agg: Dict[str, List[float]] = defaultdict(list)
    for ev in events:
        if ev["ph"] == "X":
            agg[ev["name"]].append(ev.get("dur", 0) / 1000.0)
    lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>12}"
             f"{'Max(ms)':>12}"]
    lines.append("=" * 84)
    for name, durs in sorted(agg.items(), key=lambda kv: -sum(kv[1])):
        lines.append(f"{name:<40}{len(durs):>8}{sum(durs):>12.3f}"
                     f"{sum(durs) / len(durs):>12.3f}{max(durs):>12.3f}")
    return "\n".join(lines)


def dump(finished=True, profile_process="worker"):
    """Write Chrome trace JSON (reference DumpProfile)."""
    with _LOCK:
        events = list(_EVENTS)
    with open(_CONFIG["filename"], "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return _CONFIG["filename"]


class _DurationScope:
    """Duration-event context manager base (reference profiler Task/Frame)."""

    _cat = "user"

    def __init__(self, name):
        self.name = name
        self._t0 = None

    def start(self):
        self._t0 = time.time_ns()
        return self

    def stop(self):
        if self._t0 is None:
            return
        dur = (time.time_ns() - self._t0) // 1000
        _emit(self.name, self._cat, "X", ts=self._t0 // 1000, dur=dur)
        self._t0 = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


class Task(_DurationScope):
    _cat = "task"

    def __init__(self, name, domain=None):
        super().__init__(name)


class Frame(_DurationScope):
    _cat = "frame"

    def __init__(self, name, domain=None):
        super().__init__(name)


class Event(_DurationScope):
    _cat = "event"


class Marker:
    """Instant marker (reference profiler Marker)."""

    def __init__(self, name, domain=None):
        self.name = name

    def mark(self, scope="process"):
        _emit(self.name, "marker", "i")


class Counter:
    """Named counter series (reference profiler Counter).

    Registry-backed: the value lives in the telemetry registry as
    ``profiler.<name>`` (family ``profiler.user``), so it SURVIVES a
    trace-buffer reset (``dumps(reset=True)`` clears recorded *events*,
    never declared counters) and a re-created ``Counter("x")`` resumes
    where the last one left off."""

    def __init__(self, name, domain=None, value=None):
        from . import telemetry as _telemetry

        self.name = name
        self._c = _telemetry.counter(
            f"profiler.{name}", "user profiler counter series",
            kind="gauge", family="profiler.user")
        if value is not None:
            self.set_value(value)

    @property
    def _value(self):
        return self._c.value

    def set_value(self, value):
        self._c.set(value)
        _emit(self.name, "counter", "C", args={self.name: value})

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self


class scope:
    """Annotate a profiler scope name (reference profiler.scope)."""

    def __init__(self, name="<unk>:", append_mode=False):
        self._name = name

    def __enter__(self):
        _emit(self._name, "scope", "B")
        return self

    def __exit__(self, *exc):
        _emit(self._name, "scope", "E")


# ---------------------------------------------------------------------------
# per-step phase timeline (the async pipeline engine's host-gap meter)
# ---------------------------------------------------------------------------

class StepTimeline:
    """Per-step phase breakdown of the train loop's HOST side: ``h2d``
    (taking the next batch / its device transfer wait), ``dispatch``
    (enqueueing the compiled step), ``read`` (host value reads — the AMP
    flag, metric folds), and ``host-gap`` (everything else between two
    dispatches).  Phases emit Chrome-trace duration events when the
    profiler is running AND accumulate locally, so the benchmark can use
    a timeline without enabling global collection.

    ``device_idle_gap_us`` — the headline pipeline metric — is the mean
    per-step host time spent OUTSIDE the dispatch phase: with one
    compiled program per step, whatever the host does between dispatches
    is exactly the window in which the device can run dry.  A saturated
    pipeline drives it toward zero.

    Usage::

        tl = profiler.StepTimeline()
        for batch in loader:
            with tl.phase("h2d"):
                x, y = stage(batch)
            with tl.phase("dispatch"):
                loss = step(x, y)
            tl.step()            # close the step (rest = host-gap)
        print(tl.summary())
    """

    PHASES = ("h2d", "dispatch", "host-gap", "read")

    def __init__(self, name: str = "step"):
        self.name = name
        self.steps = 0
        self.phase_ns: Dict[str, int] = defaultdict(int)
        self._step_ns = 0
        self._step_t0: Optional[int] = None
        self._accounted_ns = 0

    class _Phase:
        __slots__ = ("_tl", "_name", "_t0")

        def __init__(self, tl, name):
            self._tl = tl
            self._name = name

        def __enter__(self):
            if self._tl._step_t0 is None:
                self._tl._step_t0 = time.time_ns()
            self._t0 = time.time_ns()
            return self

        def __exit__(self, *exc):
            t1 = time.time_ns()
            dur = t1 - self._t0
            self._tl.phase_ns[self._name] += dur
            self._tl._accounted_ns += dur
            # phases are telemetry spans (cat 'step_phase'): they join
            # the unified span buffer AND the chrome-trace pipe
            from . import telemetry as _telemetry

            _telemetry.record_span(
                f"{self._tl.name}:{self._name}", "step_phase",
                self._t0, t1)

    def phase(self, name: str) -> "_Phase":
        return self._Phase(self, name)

    def step(self) -> None:
        """Close one step: everything not inside a phase() since the
        step began is the host-gap."""
        now = time.time_ns()
        if self._step_t0 is not None:
            wall = now - self._step_t0
            gap = max(0, wall - self._accounted_ns)
            self.phase_ns["host-gap"] += gap
            self._step_ns += wall
        self._accounted_ns = 0
        self._step_t0 = now
        self.steps += 1

    def summary(self) -> Dict[str, object]:
        steps = max(self.steps, 1)
        phase_us = {k: round(v / 1000.0 / steps, 1)
                    for k, v in sorted(self.phase_ns.items())}
        non_dispatch = sum(v for k, v in self.phase_ns.items()
                           if k != "dispatch")
        return {
            "steps": self.steps,
            "phase_us_per_step": phase_us,
            "wall_us_per_step": round(self._step_ns / 1000.0 / steps, 1),
            "device_idle_gap_us": round(non_dispatch / 1000.0 / steps, 1),
        }


# MXNET_PROFILER_AUTOSTART: begin collection at import, matching the
# reference's env var of the same name (profiler starts before user code so
# startup work is captured; dump() still writes the trace on demand).
def _maybe_autostart():
    from . import config

    if config.get("MXNET_PROFILER_AUTOSTART"):
        set_state("run")


_maybe_autostart()


# ---------------------------------------------------------------------------
# memory attribution (reference: GPU memory profiler mapping allocations to
# parameter names — AssignStorageInfo, src/profiler/storage_profiler.h:131)
# ---------------------------------------------------------------------------

def memory_summary(block=None, device=None, top=20) -> str:
    """Live device buffers with parameter-name attribution.

    Walks ``jax.live_arrays()``; buffers whose underlying array is a
    Parameter replica of ``block`` (or of any Block, when the parameter
    objects are supplied) are labeled with their structural name — the
    analog of the reference's storage profiler attributing GPU
    allocations to parameters.  Returns a formatted table; also usable
    for leak hunting (anonymous buffers at the top are your suspects).
    """
    import jax
    import numpy as onp

    names = {}
    if block is not None:
        for n, p in block.collect_params().items():
            for rep in (p._data or []):
                names[id(rep._data)] = n
            if p._grad is not None:
                for g in (p._grad if isinstance(p._grad, list)
                          else [p._grad]):
                    data = getattr(g, "_data", None)
                    if data is not None:
                        names.setdefault(id(data), f"{n}.grad")

    rows = []
    total = 0
    for arr in jax.live_arrays():
        if device is not None and not any(
                device in str(d) for d in arr.devices()):
            continue
        nbytes = int(onp.prod(arr.shape, dtype=onp.int64)
                     * arr.dtype.itemsize) if arr.shape else \
            arr.dtype.itemsize
        total += nbytes
        rows.append((nbytes, names.get(id(arr), "<anonymous>"),
                     tuple(arr.shape), str(arr.dtype)))
    rows.sort(reverse=True)
    # attribution is the point: named (parameter) rows always print;
    # `top` only truncates the anonymous tail
    named = [r for r in rows if r[1] != "<anonymous>"]
    anon = [r for r in rows if r[1] == "<anonymous>"]
    lines = [f"{'bytes':>12}  {'name':<32} shape dtype"]
    for nbytes, name, shape, dtype in named + anon[:top]:
        lines.append(f"{nbytes:>12}  {name:<32} {shape} {dtype}")
    lines.append(f"{total:>12}  TOTAL ({len(rows)} live buffers)")
    return "\n".join(lines)
