"""One ProgramStore: every compiled XLA executable in the process.

The reference's whole value proposition is ONE ``CachedOp`` cache that
every execution path shares (src/imperative/cached_op.cc).  We instead
grew four disconnected caches — ``cached_step.TrainStep._programs``,
``ServingEngine._programs``, the per-op eager jit cache in
``ndarray.py``, and ``HybridBlock._cached`` — four copy-pasted LRU
record/evict blocks, four counter sets, and NO persistence: bench logs
show 26–98 s per-program XLA compiles paid again on every process
start, elastic recovery, and serving deploy.

This module is the single registry those paths now resolve through:

- **Namespaces** (``train_step`` / ``serving`` / ``hybrid_forward`` /
  ``eager_jit``): each legacy cache becomes a namespace with one shared
  eviction surface and one metrics surface (hits / misses / evictions /
  traces / dispatches, :func:`stats`).  Owners hold a :class:`ScopeCache`
  (an ``OrderedDict`` with counted ``lookup``/``insert``), so a cap
  bounds programs **per owner** — two serving engines can never evict
  each other's steady-state programs.  Caps come from
  ``MXNET_PROGRAM_CACHE_CAPS`` (``"train_step=16,serving=32,..."``),
  falling back to the legacy knobs (``MXNET_COMPILED_STEP_CACHE``,
  ``MXNET_FORWARD_CACHE``) they replace.

- **AOT executables** (:func:`build`): on a cache miss the store traces
  AND compiles ahead of dispatch (``jit(...).lower(args).compile()``)
  and the :class:`Program` record owns the compiled executable —
  dispatch calls it directly, so warm-up from *abstract* shapes
  (``Trainer.precompile`` / ``ServingEngine.warmup``), steady state, and
  elastic restore share ONE code path.  The one prior system that made
  TPU deployment viable did exactly this — compiled artifacts decoupled
  from tracing (TVM, arXiv:1802.04799; Julia→TPU offline full-program
  compilation, arXiv:1810.09868).  A call whose inputs no longer match
  the compiled avals (resharded params after a topology change) falls
  back LOUDLY to the retraceable ``jitted`` callable — counted in
  ``aot_fallbacks``, never silently wrong.  ``MXNET_PROGRAM_AOT=0``
  disables the executables (records keep only the jit callable).

- **Persistence** (``MXNET_PROGRAM_CACHE_DIR``, off by default): backs
  every compile with JAX's persistent compilation cache, keyed by
  (serialized HLO, compile options, jax/jaxlib version) — a second
  process re-tracing the same signature gets a DISK hit (seconds)
  instead of a fresh XLA compile (minutes).  Hit/miss/compile-time
  counters ride on ``jax.monitoring`` (:func:`disk_stats`), so bench
  lanes can show the cold-start tax shrinking.  A corrupted or
  unreadable persistent entry degrades loudly to a fresh recompile
  under the ``program_store.load`` fault site — never a crash.
"""
from __future__ import annotations

import os
import time
import traceback
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from . import config as _config
from . import faults as _faults
from . import telemetry as _telemetry

__all__ = ["Program", "Namespace", "ScopeCache", "namespace", "scope",
           "build", "count_trace", "stats", "reset_counters", "disk_stats",
           "compile_seconds", "persistent_cache_dir", "cache_dir",
           "enable_persistent_cache", "version_fingerprint", "NAMESPACES"]


def version_fingerprint() -> Tuple[str, str, str]:
    """(jax, jaxlib, backend) — the part of every persistent key that a
    toolchain bump invalidates (JAX folds it into the disk-cache key, so
    a jaxlib upgrade can never resurrect a stale executable; it also
    means disk hits are IMPOSSIBLE across a jaxlib bump — recompile and
    re-warm)."""
    import jaxlib

    return (jax.__version__, jaxlib.__version__, jax.default_backend())


# ---------------------------------------------------------------------------
# Persistent compilation cache: enable + observe
# ---------------------------------------------------------------------------
# Disk-level counters (jax.monitoring): 'hits' = executables deserialized
# from the persistent cache instead of compiled; 'misses' = fresh XLA
# compiles that went through the (enabled) cache and were written back.
# With the cache disabled neither moves.
_DISK = {"hits": 0, "misses": 0, "requests": 0,
         "compile_time_saved_s": 0.0, "retrieval_s": 0.0}


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _DISK["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _DISK["misses"] += 1
    elif event == "/jax/compilation_cache/compile_requests_use_cache":
        _DISK["requests"] += 1


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event.endswith("compile_time_saved_sec"):
        _DISK["compile_time_saved_s"] += secs
    elif event.endswith("cache_retrieval_time_sec"):
        _DISK["retrieval_s"] += secs


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _env_cache_dir() -> Optional[str]:
    # graftlint: disable=env-discipline -- JAX_COMPILATION_CACHE_DIR is
    # jax's knob (jax reads it itself), not ours to declare
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or None


def cache_dir() -> str:
    """THE compile-cache directory — the only code in the repository
    that names one.  ``JAX_COMPILATION_CACHE_DIR`` set: that directory,
    and nothing is set in code (jax reads the variable itself, so the
    cache can be placed from outside).  Otherwise
    ``MXNET_PROGRAM_CACHE_DIR``, else ``<checkout>/.jax_cache`` derived
    from this file — a FIXED path (the directory is part of the cache
    key: one built from a pid, a time or ``tempfile`` never hits)."""
    env = _env_cache_dir()
    if env:
        return env
    d = _config.get("MXNET_PROGRAM_CACHE_DIR")
    if d:
        return os.path.expanduser(d)
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def enable_persistent_cache(min_compile_secs: float = 0) -> str:
    """Back every compile of this process with the persistent cache at
    :func:`cache_dir` (what the benchmark tools and ``chip_smoke.py``
    call; a plain ``import mxnet_tpu`` enables it only when
    ``MXNET_PROGRAM_CACHE_DIR`` asks).  Returns the live directory."""
    d = cache_dir()
    if not _env_cache_dir():
        jax.config.update("jax_compilation_cache_dir", d)
    # persist EVERYTHING by default: the parity contract (a warm second
    # process performs 0 fresh compiles) needs even sub-second CPU
    # programs and tiny eager-op executables on disk
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def _enable_persistent() -> None:
    """Apply MXNET_PROGRAM_CACHE_DIR (off by default, enabled
    per-process).  Runs at import — before any program this framework
    emits compiles — and never overrides a cache dir the user or a
    driver already configured via JAX_COMPILATION_CACHE_DIR."""
    if _config.get("MXNET_PROGRAM_CACHE_DIR") \
            and jax.config.jax_compilation_cache_dir is None:
        enable_persistent_cache()


_enable_persistent()


def persistent_cache_dir() -> Optional[str]:
    """The live persistent-cache dir (ours, the user's, or None)."""
    return jax.config.jax_compilation_cache_dir


def disk_stats() -> Dict[str, Any]:
    """Persistent-compilation-cache counters for this process."""
    out: Dict[str, Any] = dict(_DISK)
    out["dir"] = persistent_cache_dir()
    out["enabled"] = out["dir"] is not None
    return out


# ---------------------------------------------------------------------------
# Namespaces + per-owner scope caches
# ---------------------------------------------------------------------------
# every Namespace counter lives in the telemetry registry as
# 'program_store.<namespace>.<field>' (family 'program_store.namespace');
# the attribute reads/writes below stay working as properties, so every
# legacy view (cached_step.trace_count, serving.bucket_stats, ...) is now
# transitively a registry view
_NS_FIELDS = ("hits", "misses", "evictions", "traces", "dispatches",
              "aot_fallbacks", "load_degrades", "compile_count")


class Namespace:
    """One metrics + eviction surface shared by every scope of a
    program family (the dispatch-budget gate reads these uniformly)."""

    def __init__(self, name: str, cap_default: int,
                 cap_env: Optional[str] = None):
        self.name = name
        self.cap_default = cap_default
        self.cap_env = cap_env
        self._c = {f: _telemetry.counter(
            f"program_store.{name}.{f}",
            f"ProgramStore namespace {name!r}: {f}",
            family="program_store.namespace") for f in _NS_FIELDS}
        self._c["compile_seconds"] = _telemetry.counter(
            f"program_store.{name}.compile_seconds",
            f"ProgramStore namespace {name!r}: wall-clock building "
            "programs", kind="time", family="program_store.namespace")
        # weakrefs, not strong refs: a dropped owner (a dead TrainStep,
        # a closed engine) must release its programs' HBM
        self._scopes: list = []

    def bump(self, field: str, n=1) -> None:
        """Atomic counter increment (the only write path the store's
        hot paths use)."""
        self._c[field].inc(n)

    def reset(self) -> None:
        for c in self._c.values():
            c.reset()

    def cap(self) -> int:
        """Per-scope program cap: MXNET_PROGRAM_CACHE_CAPS
        ('ns=cap,...') wins, else the legacy knob, else the default."""
        spec = _config.get("MXNET_PROGRAM_CACHE_CAPS") or ""
        for part in spec.split(","):
            k, _, v = part.strip().partition("=")
            if k == self.name and v:
                try:
                    cap = int(v)
                except ValueError:
                    raise ValueError(
                        f"MXNET_PROGRAM_CACHE_CAPS entry {part!r}: cap "
                        "must be an integer")
                if cap < 1:
                    raise ValueError(
                        f"MXNET_PROGRAM_CACHE_CAPS entry {part!r}: cap "
                        "must be >= 1")
                return cap
        if self.cap_env is not None:
            return int(_config.get(self.cap_env))
        return self.cap_default

    def _live_scopes(self):
        scopes = []
        refs = []
        for r in self._scopes:
            s = r()
            if s is not None:
                scopes.append(s)
                refs.append(r)
        self._scopes = refs
        return scopes

    def _attach(self, scope_cache: "ScopeCache") -> None:
        self._live_scopes()                     # prune dead owners
        self._scopes.append(weakref.ref(scope_cache))

    def live(self) -> int:
        """Compiled programs currently held across this namespace's
        live scopes."""
        return sum(len(s) for s in self._live_scopes())

    def stats(self) -> Dict[str, Any]:
        return {
            "hits": self.hits, "misses": self.misses,
            "evictions": self.evictions, "traces": self.traces,
            "dispatches": self.dispatches, "live": self.live(),
            "cap": self.cap(), "aot_fallbacks": self.aot_fallbacks,
            "load_degrades": self.load_degrades,
            "compile_count": self.compile_count,
            "compile_seconds": round(self.compile_seconds, 3),
        }


def _ns_prop(field):
    def _get(self):
        return self._c[field].value

    def _set(self, v):
        self._c[field].set(v)

    return property(_get, _set)


for _f in _NS_FIELDS + ("compile_seconds",):
    setattr(Namespace, _f, _ns_prop(_f))
del _f


class ScopeCache(OrderedDict):
    """One owner's keyspace inside a namespace: an ``OrderedDict`` (so
    existing ``len``/iteration/``clear`` call sites and tests keep
    working) whose ``lookup``/``insert`` route hit/miss/eviction
    accounting through the namespace and enforce its cap — THE single
    implementation of the LRU record/evict block that was previously
    copy-pasted between cached_step.py and serving.py."""

    def __init__(self, ns: Namespace,
                 on_evict: Optional[Callable[[Any, Any], None]] = None):
        super().__init__()
        self._ns = ns
        self._on_evict = on_evict
        ns._attach(self)

    @property
    def namespace(self) -> Namespace:
        return self._ns

    def lookup(self, key):
        """Counted get: a hit refreshes LRU recency; a miss is the
        caller's cue to build + ``insert``."""
        rec = self.get(key)
        if rec is None:
            self._ns.bump("misses")
        else:
            self._ns.bump("hits")
            self.move_to_end(key)
        return rec

    def insert(self, key, rec):
        """Record a freshly built program and evict past the namespace
        cap (oldest first)."""
        self[key] = rec
        cap = self._ns.cap()
        while len(self) > cap:
            old_key, old_rec = self.popitem(last=False)
            self._ns.bump("evictions")
            _telemetry.event("cache_evict", self._ns.name, cap=cap)
            if self._on_evict is not None:
                self._on_evict(old_key, old_rec)
        return rec


NAMESPACES: Dict[str, Namespace] = {}


def _declare(name: str, cap_default: int,
             cap_env: Optional[str] = None) -> Namespace:
    ns = NAMESPACES.get(name)
    if ns is None:
        ns = NAMESPACES[name] = Namespace(name, cap_default, cap_env)
    return ns


# the four legacy caches, as namespaces (docs/PERF.md namespace table)
_declare("train_step", 16, cap_env="MXNET_COMPILED_STEP_CACHE")
_declare("serving", 32, cap_env="MXNET_FORWARD_CACHE")
_declare("hybrid_forward", 32, cap_env="MXNET_FORWARD_CACHE")
_declare("eager_jit", 512)
# generative serving (serving_decode.GenerativeEngine): the bounded
# program set is prefill-buckets + 1 decode per engine — the cap only
# needs to cover that grid, and per-owner caps keep co-hosted models
# from evicting each other's decode program
_declare("serving_decode", 32, cap_env="MXNET_FORWARD_CACHE")
# speculative decoding (serving_decode, MXNET_SPEC_DECODE): draft
# prefill buckets + one draft round program + one verify program per
# MXNET_SPEC_K shape — a small fixed grid, kept apart from
# serving_decode so the spec lane's program census is auditable on its
# own (check_dispatch_budget's `spec` lane)
_declare("serving_spec", 32, cap_env="MXNET_FORWARD_CACHE")


def namespace(name: str) -> Namespace:
    try:
        return NAMESPACES[name]
    except KeyError:
        raise KeyError(f"undeclared ProgramStore namespace {name!r}; "
                       f"known: {sorted(NAMESPACES)}")


def scope(name: str,
          on_evict: Optional[Callable[[Any, Any], None]] = None
          ) -> ScopeCache:
    """A new per-owner cache in ``name``'s namespace."""
    return ScopeCache(namespace(name), on_evict)


def count_trace(name: str) -> None:
    """Called from inside a program body: bumps when jax (re)traces it
    (and logs a ``retrace`` bus event with the current step index — a
    steady-state retrace is the classic silent perf killer)."""
    namespace(name).bump("traces")
    _telemetry.event("retrace", name)


# ---------------------------------------------------------------------------
# Programs: build (trace + AOT compile) and dispatch
# ---------------------------------------------------------------------------
class Program:
    """One compiled program record: the AOT executable the store owns
    plus the retraceable ``jitted`` callable behind it, and whatever
    namespace-specific ``meta`` the caller needs at dispatch."""

    __slots__ = ("executable", "jitted", "meta", "_ns")

    def __init__(self, executable, jitted, meta, ns: Namespace):
        self.executable = executable
        self.jitted = jitted
        self.meta = meta
        self._ns = ns

    def __call__(self, *args):
        self._ns.bump("dispatches")
        if self.executable is not None:
            try:
                return self.executable(*args)
            except (TypeError, ValueError) as e:
                # aval/sharding drift vs the compiled signature (both are
                # checked BEFORE execution, so nothing ran and no donated
                # buffer was consumed): fall back to the retraceable
                # callable — loud, counted, never silently wrong.  A
                # genuine error re-raises identically from the jit path.
                self._ns.bump("aot_fallbacks")
                _faults.record_event(
                    "program_store.load", "aot_fallback", e,
                    namespace=self._ns.name)
                self.executable = None
        return self.jitted(*args)


def _aot_enabled() -> bool:
    return bool(_config.get("MXNET_PROGRAM_AOT"))


class _loud_cache_errors:
    """Scoped ``jax_raise_persistent_cache_errors=True``: inside a store
    build a corrupted/unreadable persistent entry must RAISE (so the
    ``program_store.load`` degrade path sees it and logs it) instead of
    jax's default silent skip-and-recompile.  Outside builds the default
    stays False — an eager-op compile hitting a corrupt entry quietly
    recompiles, which is safe there."""

    def __enter__(self):
        self._prev = jax.config.jax_raise_persistent_cache_errors
        jax.config.update("jax_raise_persistent_cache_errors", True)

    def __exit__(self, *exc):
        jax.config.update("jax_raise_persistent_cache_errors", self._prev)


def _persistent_entry_involved(e: BaseException) -> bool:
    """True when ``e`` is an injected ``program_store.load`` fault or
    was raised while jax read/wrote a persistent-cache entry (its
    traceback passes through ``jax._src.compilation_cache``) — the only
    failures a cache-bypassing recompile can cure."""
    if isinstance(e, _faults.FaultInjected):
        return True
    return any(
        fr.f_code.co_filename.endswith("compilation_cache.py")
        for fr, _ in traceback.walk_tb(e.__traceback__))


def build(name: str, jitted, lower_args: Tuple, meta: Any = None,
          label: str = "") -> Program:
    """Trace + compile ``jitted`` for ``lower_args`` (concrete arrays
    and/or ``jax.ShapeDtypeStruct`` specs — the latter is what makes
    warm-up from abstract shapes possible) into a :class:`Program`.

    This is the ``program_store.load`` site: with a persistent cache
    enabled the compile step READS disk entries, and a corrupted or
    unreadable entry (or an injected fault) degrades LOUDLY to a fresh
    compile with the disk cache bypassed for this program — recorded in
    ``load_degrades`` + the faults event log, never a crash.  Any other
    trace/compile failure propagates to the caller untouched."""
    ns = namespace(name)
    t0 = time.perf_counter()
    executable = None
    if _aot_enabled():
        try:
            _faults.inject("program_store.load")
            with _loud_cache_errors():
                executable = jitted.lower(*lower_args).compile()
        except Exception as e:
            live_dir = persistent_cache_dir()
            if live_dir is None or not _persistent_entry_involved(e):
                # no persistent entry was in play: a real trace/compile
                # failure (a forward that cannot stage, a Mosaic
                # refusal, an HBM OOM) — it propagates, never retried
                # into a second identical failure
                raise
            ns.bump("load_degrades")
            _faults.record_event(
                "program_store.load", "degrade_to_recompile", e,
                namespace=name, label=label, cache_dir=live_dir)
            # bypass the (possibly corrupt) disk entry and compile
            # fresh; the cache comes back for every later program
            try:
                jax.config.update("jax_compilation_cache_dir", None)
                executable = jitted.lower(*lower_args).compile()
            finally:
                jax.config.update("jax_compilation_cache_dir", live_dir)
    ns.bump("compile_count")
    ns.bump("compile_seconds", time.perf_counter() - t0)
    return Program(executable, jitted, meta, ns)


def compile_seconds() -> float:
    """Wall-clock spent building programs through the store (all
    namespaces) — the in-process share of the cold-start tax."""
    return sum(ns.compile_seconds for ns in NAMESPACES.values())


def stats(name: Optional[str] = None) -> Dict[str, Any]:
    """The one metrics surface: per-namespace counters + the disk
    cache.  ``stats('train_step')`` returns a single namespace's dict."""
    if name is not None:
        return namespace(name).stats()
    out: Dict[str, Any] = {ns.name: ns.stats()
                           for ns in NAMESPACES.values()}
    out["persistent"] = disk_stats()
    out["compile_seconds"] = round(compile_seconds(), 3)
    return out


def reset_counters(name: Optional[str] = None) -> None:
    """Zero namespace counters (tests/benchmarks); live programs and
    disk-level counters are untouched."""
    if name is not None:
        namespace(name).reset()
        return
    for ns in NAMESPACES.values():
        ns.reset()
