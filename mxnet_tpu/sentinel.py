"""Training-integrity sentinel: in-program state digests, cross-replica
corruption voting, anomaly-windowed rollback, suspect-device quarantine.

PR 11 proved the stack survives being *killed*; nothing defended a job
that keeps running with *wrong bits* — a flipped mantissa in a parameter
replica, a mis-executing chip, a loss quietly diverging.  On large TPU
fleets that silent mode dominates: the job looks healthy while it burns
pod-days training garbage.  This module closes it with one invariant —
**training state is continuously attested** — threaded through the
compiled step, the mesh, the elastic loop, checkpoints, and telemetry:

1. **In-program state digests.**  Every ``MXNET_SENTINEL_EVERY``
   (default 20) steps the donated compiled :class:`~..cached_step.
   TrainStep` program additionally emits a cheap on-device fingerprint
   of the post-update parameters + optimizer state + gradient norm:
   a position-weighted bitcast fold (:func:`fold_leaves` — exact uint32
   arithmetic, so it is bit-deterministic, order-independent across
   mesh shapes, and flips on ANY single-bit perturbation) plus float
   sum / grad-norm signals.  The fingerprint rides a ``lax.cond``
   inside the ONE dispatch — 0 extra dispatches, 0 retraces, and
   non-sentinel steps never execute the fold branch.  The host read is
   deferred exactly like the PR-5 AMP gate: the pending digest is
   consumed when the NEXT sentinel dispatch is offered (its program
   retired long ago, so the read never stalls the current step) or at
   a checkpoint boundary (:meth:`Sentinel.flush`, called by
   ``run_elastic`` BEFORE every save so tainted state is never
   checkpointed).

2. **Cross-replica corruption vote.**  Under ``kvstore='tpu'`` the
   replicated parameters must be bit-identical on every mesh device,
   and the SPMD partitioner computes the replicated fold redundantly
   per device — so the digest output's ``addressable_shards`` carry
   one independently-computed fingerprint per physical replica.  On a
   sentinel read the shards vote: a minority device is *localized*
   (named in a ``corruption`` telemetry event + counted in
   ``sentinel.replica_divergence``), not merely detected.

   FSDP composition (ISSUE 18): the fold itself is mesh-shape
   INVARIANT — exact wrap-around uint32 arithmetic is associative and
   commutative, so a dp×fsdp-sharded state digests to the same integer
   as its replicated or single-chip placement (a scale event or
   topology change never fakes a verdict).  The vote, however, runs on
   the digest's post-reduce output shards, which the partitioner makes
   identical across devices — under fsdp the per-device redundancy
   that powers minority LOCALIZATION degrades to a trivially unanimous
   vote.  Detection (host-recompute mismatch, anomaly windows,
   rollback, quarantine) is unchanged; only the "which chip" attribution
   narrows to the replicated-param case.

3. **Anomaly windows + rollback.**  :class:`Window` generalizes
   ``nonfinite_anomaly`` into an EMA + z-score detector
   (``MXNET_SENTINEL_ZMAX``) over the digest's grad-norm (and any loss
   series the loop feeds via :meth:`Sentinel.observe_loss`).  A tripped
   window — or a corruption vote — makes the :class:`Sentinel` (used as
   ``run_elastic(anomaly_fn=...)``) return True, driving the EXISTING
   anomaly/rollback path under the new fault site ``sentinel.rollback``:
   restore the last digest-verified checkpoint, bit-exact replay, 0
   fresh compiles on a warm cache.

4. **Suspect-device quarantine.**  A corrupt replica (or a
   ``HeartbeatMonitor``-suspected dead rank, fed by the KVStore barrier
   deadline) lands in a persisted :class:`Quarantine` list
   (``<ckpt>/quarantine.json``, written under fault site
   ``sentinel.quarantine``).  ``parallel.spmd.resolve_mesh`` consults
   the active quarantine, so the next restart re-resolves the mesh
   *without* the suspect device — the PR-11 topology-change machinery
   (``restore(like=)`` re-placement), now triggered automatically.

Overhead is bounded by construction: the digest adds no dispatch and
no retrace, it runs only on every ``MXNET_SENTINEL_EVERY``-th step,
and its host read never waits on the step in flight.
``mxnet_tpu/drills.py`` runs the end-to-end ``bitflip_param`` and
``loss_spike`` scenarios under ``tools/check_recovery_budget.py``.
"""
from __future__ import annotations

import json
import math
import os
import threading
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as onp

from . import config as _config
from . import engine as _engine
from . import faults as _faults
from . import telemetry as _telemetry
from .log import get_logger

__all__ = ["fold_leaves", "tree_digest", "Window", "Quarantine",
           "Sentinel", "install_quarantine", "active_quarantine",
           "quarantine_ranks"]

_LOG = get_logger("mxnet_tpu.sentinel")

_DIGESTS = _telemetry.counter(
    "sentinel.digests",
    "in-program state digests read on host (one per sentinel cadence "
    "step; the deferred read consumes the PREVIOUS sentinel dispatch's "
    "fingerprint, or the pending one at a checkpoint boundary)")
_DIVERGENCE = _telemetry.counter(
    "sentinel.replica_divergence",
    "sentinel reads whose per-replica digest shards disagreed — the "
    "replicated parameters are no longer bit-identical across the mesh "
    "(a corrupt device replica, localized by the vote and named in a "
    "'corruption' event)")
_ROLLBACKS = _telemetry.counter(
    "sentinel.rollbacks",
    "sentinel verdicts that triggered the run_elastic rollback path "
    "(corruption vote or windowed loss/grad-norm anomaly) under fault "
    "site sentinel.rollback")


def _quarantined_entries() -> int:
    q = active_quarantine()
    return len(q.entries()) if q is not None else 0


_telemetry.gauge_fn(
    "sentinel.quarantined", _quarantined_entries,
    "entries (suspect devices + ranks) in the active persisted "
    "quarantine list mesh resolution excludes on restart")


# ---------------------------------------------------------------------------
# digest math (traced: runs INSIDE the compiled step program)
# ---------------------------------------------------------------------------

# FNV-1a primes reused as the leaf combiner; the per-element weights use
# Knuth's multiplicative-hash constant so a permutation of elements (not
# just a value change) moves the fold
_FNV_OFFSET = 2166136261
_FNV_PRIME = 16777619
_ELEM_WEIGHT = 2654435761


def _fold_leaf(x) -> "jnp.ndarray":
    """Position-weighted uint32 fold of one array: exact integer
    arithmetic (wrap-around sum is associative + commutative, so the
    value is independent of XLA reduction order and of the mesh shape a
    replicated leaf is placed on), and any single-bit flip of any
    element changes it."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.floating):
        if x.dtype != jnp.float32:
            # bf16/f16 embed exactly into f32, so a flipped source bit
            # still lands in the bitcast
            x = x.astype(jnp.float32)
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    elif x.dtype == jnp.bool_:
        bits = x.astype(jnp.uint32)
    else:
        bits = x.astype(jnp.uint32)
    bits = bits.ravel()
    n = int(bits.shape[0])
    if n == 0:
        return jnp.uint32(0)
    wgt = (jax.lax.iota(jnp.uint32, n) * jnp.uint32(_ELEM_WEIGHT)
           + jnp.uint32(97))
    return jnp.sum(bits * wgt, dtype=jnp.uint32)


def fold_leaves(leaves: Sequence[Any]) -> "jnp.ndarray":
    """Combine per-leaf folds into one uint32 fingerprint.  The combiner
    is order-DEPENDENT across leaves (FNV-style multiply-xor), so two
    swapped leaves change the digest; within a leaf the weighted sum is
    order-independent (mesh-invariant) but position-sensitive."""
    acc = jnp.uint32(_FNV_OFFSET)
    for leaf in leaves:
        acc = (acc * jnp.uint32(_FNV_PRIME)) ^ _fold_leaf(leaf)
    return acc


_JIT_FOLD = jax.jit(fold_leaves)


def tree_digest(tree: Any) -> int:
    """Host-callable fingerprint of an arbitrary pytree — the SAME fold
    the compiled step emits, so an in-program digest can be cross-checked
    against a host recomputation, and two processes holding bit-identical
    state produce the same integer."""
    leaves = [l for l in jax.tree_util.tree_leaves(tree) if l is not None]
    return int(_JIT_FOLD(leaves))


def program_digest(new_w, state_leaves, grads):
    """The digest tuple the compiled step emits on sentinel steps:
    (uint32 fold over post-update params + optimizer state, float32
    parameter sum, float32 global grad norm).  Traced inside the one
    program — callers wrap it in ``lax.cond`` so non-sentinel steps
    never execute it."""
    leaves = list(new_w) + [l for l in state_leaves if l is not None]
    fold = fold_leaves(leaves)
    psum = jnp.float32(0)
    for w in new_w:
        psum = psum + jnp.sum(w.astype(jnp.float32))
    g2 = jnp.float32(0)
    for g in grads:
        g2 = g2 + jnp.sum(jnp.square(g.astype(jnp.float32)))
    return fold, psum, jnp.sqrt(g2)


def zero_digest():
    """The non-sentinel branch of the in-program ``lax.cond``."""
    return jnp.uint32(0), jnp.float32(0), jnp.float32(0)


# ---------------------------------------------------------------------------
# windowed anomaly detection (the nonfinite_anomaly generalization)
# ---------------------------------------------------------------------------

class Window:
    """EMA + z-score anomaly window over one scalar series.

    ``update(v)`` returns True when ``v`` is non-finite (the classic
    divergence ``nonfinite_anomaly`` caught) or, once ``min_count``
    clean observations seeded the window, when ``|v - ema| >
    zmax * std``.  Anomalous values are NOT absorbed into the window —
    a spike cannot normalize itself."""

    def __init__(self, zmax: Optional[float] = None, decay: float = 0.2,
                 min_count: int = 3):
        self.zmax = float(_config.get("MXNET_SENTINEL_ZMAX")
                          if zmax is None else zmax)
        self.decay = float(decay)
        self.min_count = int(min_count)
        self.reset()

    def reset(self) -> None:
        self.count = 0
        self.mean = 0.0
        self.var = 0.0

    def update(self, v: float) -> bool:
        v = float(v)
        if not math.isfinite(v):
            return True
        if self.count >= self.min_count:
            std = math.sqrt(self.var) + 1e-12 + 1e-9 * abs(self.mean)
            if abs(v - self.mean) > self.zmax * std:
                return True
        if self.count == 0:
            self.mean = v
        a = self.decay
        d = v - self.mean
        self.mean += a * d
        self.var = (1.0 - a) * (self.var + a * d * d)
        self.count += 1
        return False


# ---------------------------------------------------------------------------
# quarantine (persisted suspect list; consumed by mesh resolution)
# ---------------------------------------------------------------------------

class Quarantine:
    """Persisted list of suspect devices/ranks.  Entries are dicts
    ``{"kind": "device"|"rank", "id": int, "reason": str}`` in a JSON
    file (atomic replace, written under fault site
    ``sentinel.quarantine``).  A corrupt replica (sentinel vote) and a
    hung host (``HeartbeatMonitor`` via the KVStore barrier deadline)
    land in the SAME list, and ``parallel.spmd.resolve_mesh`` excludes
    both kinds on the next mesh resolve."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._lock = threading.Lock()
        self._entries: List[Dict[str, Any]] = []
        if path is not None and os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
                self._entries = [e for e in data
                                 if isinstance(e, dict) and "kind" in e]
            except (OSError, ValueError) as e:
                _LOG.warning("unreadable quarantine list %s (%r); "
                             "starting empty", path, e)

    def entries(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self._entries]

    def device_ids(self) -> List[int]:
        return sorted({e["id"] for e in self.entries()
                       if e["kind"] == "device"})

    def ranks(self) -> List[int]:
        return sorted({e["id"] for e in self.entries()
                       if e["kind"] == "rank"})

    def _add(self, kind: str, ident: int, reason: str) -> bool:
        with self._lock:
            for e in self._entries:
                if e["kind"] == kind and e["id"] == ident:
                    return False
            self._entries.append(
                {"kind": kind, "id": int(ident), "reason": reason})
        self._persist()
        _LOG.warning("quarantined %s %d (%s)", kind, ident, reason)
        return True

    def add_device(self, device_id: int, reason: str = "") -> bool:
        return self._add("device", device_id, reason)

    def add_rank(self, rank: int, reason: str = "") -> bool:
        return self._add("rank", rank, reason)

    def suspects_device(self, device) -> bool:
        """True when ``device`` (anything with ``.id`` and
        ``.process_index``) is excluded — quarantined by device id, or
        belonging to a quarantined rank."""
        with self._lock:
            for e in self._entries:
                if e["kind"] == "device" and e["id"] == device.id:
                    return True
                if e["kind"] == "rank" \
                        and e["id"] == getattr(device, "process_index", 0):
                    return True
        return False

    def filter_devices(self, devices: Sequence) -> List:
        """The mesh-resolution filter: devices minus every suspect."""
        return [d for d in devices if not self.suspects_device(d)]

    def _persist(self) -> None:
        if self.path is None:
            return
        _faults.retry_call(self._persist_once, site="sentinel.quarantine")

    def _persist_once(self) -> None:
        with self._lock:
            data = json.dumps(self._entries)
        tmp = f"{self.path}.{os.getpid()}.tmp"
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        try:
            with open(tmp, "w") as f:
                f.write(data)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise


# the process-wide active quarantine mesh resolution consults (installed
# by Sentinel construction, or directly via install_quarantine)
_ACTIVE: List[Optional[Quarantine]] = [None]


def install_quarantine(q: Optional[Quarantine]) -> Optional[Quarantine]:
    """Install (or, with None, clear) the process-wide quarantine list
    ``parallel.spmd.resolve_mesh`` and the barrier-deadline hookup
    consult."""
    _ACTIVE[0] = q
    return q


def active_quarantine() -> Optional[Quarantine]:
    return _ACTIVE[0]


def quarantine_ranks(ranks: Sequence[int], reason: str = "") -> int:
    """Feed suspected-dead ranks (a ``HeartbeatMonitor`` verdict from
    the KVStore barrier deadline) into the active quarantine — a hung
    host and a corrupt host converge on one restart-time exclusion
    mechanism.  No-op (returns 0) when no quarantine is installed."""
    q = active_quarantine()
    if q is None:
        return 0
    added = 0
    for r in ranks:
        if q.add_rank(int(r), reason or "suspected dead"):
            added += 1
    return added


# ---------------------------------------------------------------------------
# the sentinel
# ---------------------------------------------------------------------------

class Sentinel:
    """The training-integrity monitor — attach to a compiled
    :class:`~..cached_step.TrainStep` and pass as
    ``run_elastic(anomaly_fn=...)``::

        step = trainer.compile_step(net, loss_fn)
        snt = sentinel.Sentinel(step=step, directory=ckpt_dir)
        run_elastic(step_fn, state, inputs, ckpt, anomaly_fn=snt, ...)

    Per compiled dispatch the step asks :meth:`want_digest` (True every
    ``every`` calls) and hands the emitted device digest to
    :meth:`offer`; ``offer`` first consumes the PREVIOUS pending digest
    (deferred read — that program retired a full cadence ago), votes
    the per-replica shards, updates the anomaly windows, and latches a
    verdict.  ``run_elastic`` reads the verdict via ``__call__`` (the
    anomaly_fn protocol, evaluated on the ``every`` cadence) and via
    :meth:`flush` immediately BEFORE each checkpoint save — so a
    tainted state is never checkpointed and the rollback target is
    always digest-verified.  ``every=0`` (or ``MXNET_SENTINEL_EVERY=0``)
    disables the sentinel entirely."""

    def __init__(self, step=None, directory: Optional[str] = None,
                 every: Optional[int] = None, zmax: Optional[float] = None,
                 strikes: Optional[int] = None,
                 loss_window: bool = True,
                 quarantine: Optional[Quarantine] = None):
        self.every = int(_config.get("MXNET_SENTINEL_EVERY")
                         if every is None else every)
        self.strikes = int(_config.get("MXNET_SENTINEL_STRIKES")
                           if strikes is None else strikes)
        self._gnorm = Window(zmax=zmax)
        self._loss = Window(zmax=zmax) if loss_window else None
        self._calls = 0            # compiled dispatches seen
        self._pending = None       # (fold_arr, psum_arr, gnorm_arr, call)
        self._tripped: Optional[Dict[str, Any]] = None
        self._strike_counts: Dict[int, int] = {}
        self.last_fold: Optional[int] = None
        self.last_gnorm: Optional[float] = None
        self.last_psum: Optional[float] = None
        self.last_vote: Optional[Dict[str, Any]] = None
        self.last_rollback: Optional[Dict[str, Any]] = None
        if quarantine is not None:
            self.quarantine = quarantine
        elif directory is not None:
            self.quarantine = Quarantine(
                os.path.join(directory, "quarantine.json"))
        else:
            self.quarantine = Quarantine(None)
        install_quarantine(self.quarantine)
        if step is not None:
            step.attach_sentinel(self)
        _engine.register_drainable(self)

    # -- TrainStep side ---------------------------------------------------
    def want_digest(self) -> bool:
        """Called once per compiled dispatch; True on sentinel steps."""
        if self.every <= 0:
            return False
        self._calls += 1
        return self._calls % self.every == 0

    def offer(self, fold, psum, gnorm) -> None:
        """Receive the just-dispatched sentinel digest (device arrays,
        unread).  The previously pending digest — whose program retired
        a cadence ago, so the read is lagged and never stalls the
        current step — is consumed first."""
        prev, self._pending = self._pending, (fold, psum, gnorm,
                                              self._calls)
        if prev is not None:
            self._consume(prev)

    def observe_loss(self, value) -> None:
        """Optional: feed an ALREADY-READ host loss value (zero extra
        syncs) into the loss anomaly window."""
        if self._loss is None or self._tripped is not None:
            return
        if self._loss.update(float(value)):
            self._trip("loss_anomaly", value=float(value))

    # -- run_elastic side -------------------------------------------------
    def __call__(self, state=None) -> bool:
        """The ``run_elastic(anomaly_fn=...)`` protocol: True when a
        verdict (corruption vote or windowed anomaly) is latched.  The
        ``sentinel.rollback`` injection site fires here, so a fault
        plan exercises exactly the rollback recovery path."""
        _faults.inject("sentinel.rollback")
        return self._take_verdict()

    def flush(self) -> bool:
        """Consume any pending digest NOW (one blocking read) and
        return the verdict — ``run_elastic`` calls this immediately
        before every checkpoint save, so a state the sentinel rejects
        is never written and every rollback target is attested."""
        pending, self._pending = self._pending, None
        if pending is not None:
            self._consume(pending)
        return self._take_verdict()

    def drain(self) -> None:
        """engine.waitall() hook: consume the pending digest so a
        drained process' verdict/telemetry is complete.  Never raises a
        verdict — the loop (or the next flush) reports it."""
        pending, self._pending = self._pending, None
        if pending is not None:
            try:
                self._consume(pending)
            except Exception as e:      # a drain must never wedge
                _LOG.warning("sentinel drain read failed: %r", e)

    def reset_window(self) -> None:
        """Forget window state + pending digests (rollback landed: the
        restored trajectory re-seeds the EMAs)."""
        self._gnorm.reset()
        if self._loss is not None:
            self._loss.reset()
        self._pending = None

    # -- internals --------------------------------------------------------
    def _take_verdict(self) -> bool:
        tripped, self._tripped = self._tripped, None
        if tripped is None:
            return False
        self.last_rollback = tripped
        _ROLLBACKS.inc()
        _faults.record_event("sentinel.rollback", "rollback", **tripped)
        self.reset_window()
        return True

    def _trip(self, reason: str, **info) -> None:
        if self._tripped is None:
            self._tripped = dict(info, reason=reason)

    def _consume(self, pending) -> None:
        fold, psum, gnorm, _call = pending
        from .ndarray import ndarray as _ndmod

        _ndmod.count_host_sync()
        _DIGESTS.inc()
        # per-replica shard values: under a mesh each device computed
        # the replicated fold REDUNDANTLY from its own physical param
        # replica, so disagreement here IS replica divergence
        shards = sorted(
            ((s.device, int(onp.asarray(s.data).item()))
             for s in fold.addressable_shards),
            key=lambda t: t[0].id)
        values = [v for _d, v in shards]
        counts: Dict[int, int] = {}
        for v in values:
            counts[v] = counts.get(v, 0) + 1
        majority = max(counts, key=lambda v: counts[v])
        self.last_fold = majority
        suspects = [d for d, v in shards if v != majority]
        self.last_vote = {
            "devices": [d.id for d, _v in shards],
            "values": values,
            "majority": majority,
            "suspects": [d.id for d in suspects],
        }
        if suspects:
            _DIVERGENCE.inc()
            by_id = {d.id: v for d, v in shards}
            for dev in suspects:
                n = self._strike_counts.get(dev.id, 0) + 1
                self._strike_counts[dev.id] = n
                _telemetry.event(
                    "corruption", "sentinel", device=dev.id,
                    strikes=n, majority=majority, value=by_id[dev.id])
                if n >= self.strikes:
                    self.quarantine.add_device(
                        dev.id, f"replica divergence x{n} "
                                f"(digest != majority {majority})")
            _LOG.error(
                "cross-replica digest vote: device(s) %s diverged from "
                "majority %d — rolling back to the last verified "
                "checkpoint", [d.id for d in suspects], majority)
            self._trip("replica_divergence",
                       devices=[d.id for d in suspects])
            return
        # clean vote: update the anomaly windows with the float signals
        # (median across shards — replicated post-all-reduce values are
        # normally identical; the median stays sane even if one shard's
        # float path drifted without moving the exact fold)
        g = float(onp.median([onp.asarray(s.data)
                              for s in gnorm.addressable_shards]))
        self.last_gnorm = g
        self.last_psum = float(onp.median(
            [onp.asarray(s.data) for s in psum.addressable_shards]))
        if self._gnorm.update(g):
            self._trip("grad_norm_anomaly", value=g)
