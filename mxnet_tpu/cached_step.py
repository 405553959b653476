"""Compiled whole-train-step: the CachedOp analog for TRAINING.

The reference funnels every execution mode through ``CachedOp``
(``src/imperative/cached_op.cc``): a shape-keyed graph cache whose forward
AND backward run as one engine-scheduled graph each.  Our eager training
path, by contrast, still ran as a per-op vjp tape — forward dispatching
op-by-op, ``autograd.backward`` pushing one XLA program per ``TapeNode``,
and only the optimizer update fused (PR 1).  On chip every eager dispatch
pays a host round-trip (docs/PERF.md: BatchNorm 82 ms plain vs 0.3 ms
compiled), and the remaining ResNet reduce/copy texture (~37% of device
time) only fuses away when XLA sees forward and backward in ONE program.

:class:`TrainStep` (``Trainer.compile_step(net, loss_fn)``) closes that
gap: loss-fn forward (via the same staging machinery that backs
``HybridBlock.hybridize()`` — ``gluon.block._stage_fn``), the ``jax.vjp``
backward, the kvstore ``device``-path gradient reduction (an identity
reduce for the supported single-replica topology — multi-worker falls
back), the PR-1 functional ``Optimizer.fused_update`` rule
(``optimizer.fused.group_step_fn``, same numerics as the eager fused
path), and the AMP loss-scaling / all-finite gate all trace into ONE
``jax.jit`` program with DONATED parameter/optimizer-state buffers.

Programs are cached per ``TrainStep`` keyed by (input structure +
shapes/dtypes, train-mode, optimizer hyper-param signature, parameter/
state shapes+dtypes, AMP generation) — exactly CachedOp's shape-keyed
graph cache.  Per-step values (lr, wd, update counts, rescale_grad, the
loss scale) ride in as traced arguments, so an LR-scheduler tick or a
changed batch size never re-traces.

Result: dispatches/step drop from O(#tape nodes + #groups) to **1**
(+1 host scalar read for the AMP all-finite flag).  Anything the program
cannot express — a forward that cannot stage (host reads, data-dependent
shapes), ``grad_req='add'``, multi-replica parameters, dist/ps-lite
kvstores, server-side (``update_on_kvstore``) updates, optimizers without
a ``fused_update`` rule — falls back transparently to the eager tape;
``MXNET_COMPILED_STEP=0`` forces the tape everywhere.

**Pod-scale SPMD** (``kvstore='tpu'``): with an ICI-collective store the
step traces under a named ``jax.sharding.Mesh`` (``parallel.spmd``,
knob ``MXNET_SPMD_MESH``): the batch shards over the ``'dp'`` axis and
the gradient reduce this program already contains becomes an ICI-native
all-reduce scheduled by the XLA SPMD partitioner — overlappable with
backward, still ONE dispatch per step, still donated buffers.  Existing
Trainer code gets it by passing ``kvstore='tpu'``; the mesh (axes +
exact device set) is part of the program-cache key, inputs already
staged with the batch sharding (``engine.DevicePrefetcher``) pass
through without a copy, and steady state performs zero host-side
cross-device copies (``parallel.spmd.reshard_count``, pinned by the
dispatch-budget gate).  Host-driven stores (``dist_*``) still fall
back, naming this path.

**Beyond one chip's HBM** the same one-program contract extends to the
model-parallel axes and to gradient accumulation:

- an ``fsdp`` mesh axis (``MXNET_SPMD_MESH='dp=4,fsdp=2'``) shards
  parameters AND optimizer state at warmup (``spmd.param_spec``:
  largest evenly-divisible dim, indivisible leaves replicate loudly via
  ``sharding.legalize_refusal``); the per-leaf scatter/gather around
  the update is the XLA partitioner's schedule inside the one donated
  program — per-device param bytes drop ~1/N (gauges
  ``spmd.param_bytes_per_device`` / ``spmd.opt_bytes_per_device``);
- a ``tp`` axis honors model-code ``sharding.constraint`` annotations:
  the step traces AND dispatches inside the mesh context, so a
  constraint in a hybridizable forward resolves axis names without the
  mesh threaded through — composing with FSDP on the same mesh;
- ``Trainer.compile_step(..., accum_steps=N)`` splits the step into a
  grad-accumulation program (dispatched per microbatch, donated
  accumulator buffers sharded like their parameters) and ONE fused
  update program per window — exactly N+1 dispatches per window, the
  deferred AMP gate spanning the window (scale held fixed across it,
  overflow detected on the summed grads), lr/update-count semantics
  identical to one big-batch step.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import autograd
from . import config as _config
from . import engine as _engine
from . import faults as _faults
from . import program_store as _pstore
from . import random as _random
from . import telemetry as _telemetry
from .context import current_context

__all__ = ["TrainStep", "enabled", "trace_count", "dispatch_count",
           "cache_stats", "deferred_read_count", "reset_counters"]

# observability: this module's programs live in the ProgramStore
# 'train_step' namespace — traces bump when a whole-step program body is
# (re)traced, dispatches per compiled launch, hits/misses/evictions
# track the shape-keyed program cache.  The module-level functions below
# are views over that one shared surface (tools/check_dispatch_budget.py
# and benchmark/eager_latency.py read them; the bar: 1 dispatch/step,
# 0 retraces after warm-up).
_NS = _pstore.namespace("train_step")
_DEFERRED_READ = _telemetry.counter(
    "cached_step.deferred_read",
    "host reads of a LAGGED all-finite flag (the deferred AMP gate, "
    "MXNET_AMP_LAG): each reads step N-1's flag while step N is in "
    "flight, so it never blocks the current program")


def trace_count() -> int:
    return _NS.traces


def dispatch_count() -> int:
    return _NS.dispatches


def cache_stats() -> Dict[str, int]:
    return {"hits": _NS.hits, "misses": _NS.misses,
            "evictions": _NS.evictions}


def deferred_read_count() -> int:
    """Host reads of a LAGGED all-finite flag (the deferred AMP gate,
    MXNET_AMP_LAG): each is a read of step N-1's flag performed while
    step N is already in flight, so it never blocks on the current
    program.  (View over the ``cached_step.deferred_read`` registry
    counter.)"""
    return int(_DEFERRED_READ.value)


def reset_counters() -> None:
    _NS.reset()
    _DEFERRED_READ.reset()


def enabled() -> bool:
    """Compiled-step knob on (MXNET_COMPILED_STEP, default 1)."""
    return bool(_config.get("MXNET_COMPILED_STEP"))


# ---------------------------------------------------------------------------
# Device scopes of the step programs (docs/OBSERVABILITY.md, "Device
# scopes").  What a step program does outside the net's and the loss's
# blocks is traced under one of these jax.named_scope names, so every HLO
# instruction's op_name says which part of the step it belongs to; an
# operation under neither a block nor one of these counts as unscoped
# (perfbench: kernel.unscoped_share).  Scopes are entered while a program
# is traced, never on a dispatch.
# ---------------------------------------------------------------------------
STEP_SCOPES = (
    _pstore.UPDATE_SCOPE,   # the fused optimizer groups: pass "update"
    "grad_finite_check",    # gradient casts and the all-finite reduction
    "loss_scale",           # scale selection and the scaling of the heads
    "sentinel_digest",      # the lax.cond state fingerprint
    "grad_accumulate",      # accum_steps > 1: the add into / zeroing of
)                           # the window's accumulators


def _named(fn, kind: str, net):
    """Name a step program's body ``mx_<kind>__<Net>``: jax names the HLO
    module ``jit_<that>``, so an ``XLA Modules`` event of a device trace
    says which program ran, and the module name is part of jax's
    compile-cache key (location metadata is not: without a name of its own
    a cache filled before the scopes existed would hand back executables
    that carry none)."""
    fn.__name__ = fn.__qualname__ = f"mx_{kind}__{type(net).__name__}"
    return fn


def _all_finite(grads, has_ok):
    with jax.named_scope("grad_finite_check"):
        if has_ok and grads:
            return jnp.all(jnp.stack([jnp.isfinite(g).all()
                                      for g in grads]))
        return jnp.asarray(True)


def _fused_update(bodies, group_layout, w_list, grads, s_list,
                  lrs_g, wds_g, counts_g, rescale_eff, ok):
    """One fused optimizer body a (multi-precision, plain) group."""
    new_w, new_s = list(w_list), list(s_list)
    with jax.named_scope(_pstore.UPDATE_SCOPE):
        for gi, (_mp, members) in enumerate(group_layout):
            nw, ns = bodies[gi](
                [w_list[i] for i in members],
                [grads[i] for i in members],
                [s_list[i] for i in members],
                lrs_g[gi], wds_g[gi], counts_g[gi], rescale_eff, ok)
            for j, i in enumerate(members):
                new_w[i] = nw[j]
                new_s[i] = ns[j]
    return new_w, new_s


def _digest(want_digest, new_w, new_s, grads):
    from . import sentinel as _sentinel

    with jax.named_scope("sentinel_digest"):
        state_leaves = jax.tree_util.tree_leaves(tuple(new_s))
        return jax.lax.cond(
            want_digest,
            lambda: _sentinel.program_digest(new_w, state_leaves, grads),
            _sentinel.zero_digest)


class TrainStep:
    """One training step — forward, backward, reduce, update — as one
    compiled, donated XLA program (``Trainer.compile_step``).

    ``loss_fn(net, *args)`` must return NDArray loss value(s); calling the
    step runs the whole update and returns the (unscaled) loss.  The
    backward seeds ones over every loss leaf, exactly like
    ``autograd.backward`` on the eager tape, so ``step(x, y)`` is the
    compiled equivalent of::

        with autograd.record():
            loss = loss_fn(net, x, y)
        loss.backward()
        trainer.step(batch_size)

    Parameter ``.grad()`` buffers are NOT materialized on the compiled
    path (gradients live only inside the program, and the step releases
    the buffers — ``Parameter._release_grad``); the eager fallback
    re-creates and writes them as usual.
    """

    def __init__(self, net, loss_fn: Callable, trainer, bucket: bool = False,
                 accum_steps: int = 1):
        self._net = net
        self._loss_fn = loss_fn
        self._trainer = trainer
        # gradient accumulation (compile_step(accum_steps=N)): N
        # microbatch grad dispatches feed donated accumulator buffers,
        # then ONE fused update applies the window — N+1 dispatches,
        # one optimizer update-count bump, per window
        if int(accum_steps) < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        self._accum_steps = int(accum_steps)
        self._accum_bufs: Optional[list] = None
        self._accum_key = None
        self._accum_i = 0
        # this step's keyspace in the ProgramStore 'train_step'
        # namespace: shared eviction (cap MXNET_COMPILED_STEP_CACHE /
        # MXNET_PROGRAM_CACHE_CAPS) + shared metrics, per-instance keys
        self._programs = _pstore.scope("train_step")
        # sticky: set on a staging/trace failure — the forward cannot
        # stage, so every later call takes the eager tape directly
        self.fallback_reason: Optional[str] = None
        # why the LAST call fell back (None when it ran compiled)
        self.last_fallback_reason: Optional[str] = None
        # shape bucketing (serving.BucketPolicy, MXNET_SHAPE_BUCKETS),
        # opt-in: variable-length batches pad up to the bucket grid so
        # they stop blowing the shape-keyed program cache.  The loss must
        # be PAD-SAFE (masked so zero rows contribute nothing — e.g. the
        # DataLoader last_batch='pad' valid count turned into a mask);
        # the first use of each bucket verifies the padded loss value
        # against the unpadded one and REFUSES bucketing on mismatch
        # (sticky, reason in bucket_refused) — numerics never change
        # silently.
        # graftlint: disable=host-sync -- host python flag, not a device read
        self._bucket = bool(bucket)
        self.bucket_refused: Optional[str] = None
        self._bucket_verified: set = set()
        self.padded_steps = 0
        # SPMD mesh (kvstore='tpu', MXNET_SPMD_MESH): resolved once the
        # kvstore exists (first __call__); None = single-chip path
        self._mesh = None
        self._mesh_resolved = False
        # deferred AMP gate (MXNET_AMP_LAG): the previous step's device
        # all-finite flag, not yet read on host.  The NEXT dispatch
        # carries both scale candidates and selects on this flag
        # on-device; the host read then happens while that dispatch is
        # in flight.  engine.waitall() drains it via drain().
        self._pending_ok = None
        # training-integrity sentinel (mxnet_tpu/sentinel.py): when
        # attached, sentinel-cadence dispatches flip the traced
        # want_digest flag so the program's lax.cond emits the state
        # fingerprint — same program, 0 extra dispatches/retraces
        self._sentinel = None
        _engine.register_drainable(self)

    def attach_sentinel(self, sentinel):
        """Attach a :class:`mxnet_tpu.sentinel.Sentinel`: it decides the
        digest cadence (``want_digest`` per compiled dispatch) and
        receives the emitted device fingerprint via ``offer``."""
        self._sentinel = sentinel
        return sentinel

    # -- public ----------------------------------------------------------
    @property
    def last_step_compiled(self) -> bool:
        return self.last_fallback_reason is None

    @property
    def mesh(self):
        """The SPMD mesh this step traces under (``None`` single-chip)."""
        return self._mesh

    @property
    def batch_sharding(self):
        """The ``NamedSharding`` input batches should be staged with —
        hand it to ``engine.prefetch(..., sharding=)`` / ``DataLoader(...,
        sharding=)`` so the prefetch thread's ``device_put`` already
        lands shards on the mesh and the step pays no re-placement.
        ``None`` when the step is single-chip."""
        if not self._mesh_resolved and not self._trainer._kv_initialized:
            self._trainer._init_kvstore()    # the mesh follows the store
        if self._resolve_mesh() is None:
            return None
        from .parallel import spmd as _spmd

        return _spmd.batch_sharding(self._mesh)

    def _params_on_mesh(self) -> bool:
        """True once the compiled mesh path actually replicated the
        parameters across >1 device (a fallback BEFORE placement keeps
        plain single-device eager semantics)."""
        for p in self._trainer._params:
            if p.grad_req == "null" or p._data is None:
                continue
            sh = getattr(p.data()._data, "sharding", None)
            return sh is not None and len(sh.device_set) > 1
        return False

    def _platform(self) -> str:
        """The platform the step runs on — the mesh's, else the
        parameters' context's (where :meth:`_prep` places every operand)
        — not the process default."""
        if self._mesh is not None:
            return self._mesh.devices.flat[0].platform
        for p in self._trainer._params:
            if p._data is not None:
                return p.data().ctx.jax_device.platform
        return current_context().jax_device.platform

    def _resolve_mesh(self):
        if not self._mesh_resolved:
            from .parallel import spmd as _spmd

            kv = self._trainer._kvstore
            self._mesh = _spmd.mesh_for_store(
                getattr(kv, "type", None)) if kv is not None else None
            self._mesh_resolved = True
        return self._mesh

    def drain(self) -> None:
        """Read the pending deferred AMP flag (if any) and apply the
        loss-scale policy, catching the host scaler state up to the
        device.  Called by ``engine.waitall()``, before any eager-tape
        fallback, and whenever the lag window closes (MXNET_AMP_LAG=0 /
        NaiveEngine) — after drain() the scaler state equals the
        synchronous gate's bit-exactly."""
        prev, self._pending_ok = self._pending_ok, None
        if prev is None:
            return
        from .ndarray import ndarray as _ndmod

        _ndmod.count_host_sync()
        _DEFERRED_READ.inc()
        scaler = getattr(self._trainer, "_amp_loss_scaler", None)
        if scaler is not None:
            with _telemetry.span("train_step.gate", cat="train_step",
                                 args={"where": "drain"}):
                # graftlint: disable=host-sync -- the deliberate deferred
                # AMP gate read at drain time, counted via
                # count_host_sync above
                overflow = not bool(prev)
                if overflow:
                    _telemetry.event("amp_overflow", "cached_step",
                                     where="drain")
                scaler.update_scale(overflow)

    def __call__(self, *args, batch_size: Optional[int] = None):
        # train-step injection site (fail-fast like trainer.step: a step
        # is not idempotent; recovery is restore-and-replay, not retry)
        _faults.inject("cached_step.step")
        step_idx = _telemetry.next_step()
        with _telemetry.span("train_step.step", cat="train_step") as sp:
            # the step's host phases (docs/OBSERVABILITY.md, "Host
            # phases"): children of this span, one after the other
            ph = _telemetry.phases("train_step")
            ph.to("train_step.prep")
            out = self._call_inner(args, batch_size, step_idx, sp, ph)
            # closed here, not where the step's work ends: the last phase
            # also holds the frames' teardown (the references to the
            # donated buffers are dropped as _compiled_step returns)
            ph.end()
            return out

    def _call_inner(self, args, batch_size, step_idx, sp, ph):
        tr = self._trainer
        if batch_size is None:
            batch_size = int(args[0].shape[0]) \
                if args and getattr(args[0], "shape", ()) else 1
        if not tr._kv_initialized:
            tr._init_kvstore()
        if tr._params_to_init:
            tr._init_params()
        reason = self._eligibility()
        if reason is not None:
            if reason != self.last_fallback_reason:
                _telemetry.event("fallback", "cached_step", reason=reason)
            self.last_fallback_reason = reason
            sp.annotate(path="eager", step=step_idx)
            ph.drop()             # the eager tape has no phases
            return self._eager_step(args, batch_size)
        opt = tr._optimizer
        # host-side update-count bump BEFORE reading lrs (the eager order:
        # Optimizer.update -> _update_count -> _get_lrs); snapshotted so a
        # build failure can roll back before the eager fallback re-bumps
        indices = [tr._param2idx[id(p)] for p in tr._params
                   if p.grad_req != "null"]
        count_snap = (dict(opt._index_update_count), opt.num_update)
        pargs = self._maybe_pad(args)
        # with accumulation only the window-FINAL microbatch applies an
        # update, so only it bumps the counts — lr schedules and
        # momentum counts see one step per window, not per microbatch
        window_final = (self._accum_steps == 1
                        or self._accum_i == self._accum_steps - 1)
        if window_final:
            opt._update_count(list(indices))
        try:
            out = self._compiled_step(pargs, batch_size, ph)
        except Exception as e:  # staging/trace failure -> sticky fallback
            ph.drop()
            opt._index_update_count.clear()
            opt._index_update_count.update(count_snap[0])
            opt.num_update = count_snap[1]
            if self._platform() != "cpu":
                # on an accelerator a step that cannot compile or
                # dispatch (Mosaic refusal, HBM OOM, operand/executable
                # device mismatch) must never become a slow run that
                # exits 0: only the DECLARED ineligible set-ups
                # (_eligibility) take the eager tape there
                raise
            self.fallback_reason = f"{type(e).__name__}: {e}"
            self.last_fallback_reason = self.fallback_reason
            _telemetry.event("fallback", "cached_step",
                             reason=self.fallback_reason, sticky=True)
            sp.annotate(path="eager", step=step_idx)
            return self._eager_step(args, batch_size)
        self.last_fallback_reason = None
        sp.annotate(path="compiled", step=step_idx)
        return out

    # -- shape bucketing --------------------------------------------------
    def _maybe_pad(self, args):
        """Pad the batch axis of every input leaf up to its bucket
        (``serving.BucketPolicy``) so variable-length batches share one
        program per bucket.  Applies only with ``compile_step(...,
        bucket=True)``; verified once per bucketed signature (the padded
        loss must equal the unpadded loss up to summation order — a
        pad-safe/masked loss), refused sticky otherwise.  Returns the
        (possibly padded) args; the eager fallback always sees the
        ORIGINAL args."""
        if not self._bucket or self.bucket_refused is not None:
            return args
        try:
            from . import serving as _serving
            from .gluon import block as _gb
            from .ndarray.ndarray import _wrap

            policy = _serving.BucketPolicy()
            if not policy.enabled:
                return args
            leaves, struct = _gb._flatten_args(args)
            if not leaves or any(len(l.shape) < 1 for l in leaves):
                return args
            n = int(leaves[0].shape[0])
            b = policy.bucket(n)
            if b is None or b == n:
                return args
            key = (_gb._struct_key(struct), b,
                   tuple((tuple(l.shape), str(l._data.dtype))
                         for l in leaves))
            pad = [_wrap(_serving.pad_axis0(l._data, b), l.ctx, type(l))
                   if int(l.shape[0]) == n else l for l in leaves]
            pargs = _gb._unflatten_args(struct, pad)
            if _config.get("MXNET_SERVE_VERIFY") and \
                    key not in self._bucket_verified:
                reason = self._verify_pad(args, pargs)
                if reason is not None:
                    self.bucket_refused = reason
                    return args
                self._bucket_verified.add(key)
            self.padded_steps += 1
            return tuple(pargs)
        except Exception as e:
            self.bucket_refused = f"{type(e).__name__}: {e}"
            return args

    def _verify_pad(self, args, pargs) -> Optional[str]:
        """One loss-only eager evaluation of both the true and the padded
        batch (recording off, train mode, parameter buffers snapshotted
        and restored so a mutating forward — BN batch stats — cannot
        leak).  Equal loss values prove the loss masks pad rows; a
        difference refuses bucketing BEFORE a single padded gradient is
        applied.  Equal means up to 64 eps of the loss's dtype: XLA
        tiles a reduction by its operand's shape, so the zero rows of
        the padded batch change the ORDER of the sum (1 ulp seen for 12
        terms against 16), while a loss that does not mask them is off
        by a pad row's whole share."""
        import numpy as onp

        from .gluon import block as _gb

        reps = [d for p in self._net.collect_params().values()
                if p._data is not None for d in p._data]
        snap = [(d, d._data, d._version) for d in reps]
        try:
            with autograd.pause(train_mode=True):
                lt = self._loss_fn(self._net, *args)
                lp = self._loss_fn(self._net, *pargs)
        finally:
            for d, old, ver in snap:
                d._data = old
                d._version = ver
        lt_leaves, _ = _gb._flatten_output(lt)
        lp_leaves, _ = _gb._flatten_output(lp)
        if len(lt_leaves) != len(lp_leaves):
            return "padded loss structure differs from unpadded"
        for t, p in zip(lt_leaves, lp_leaves):
            # graftlint: disable=host-sync -- one-time pad-safety verify
            # per bucket signature, off the steady-state step path
            tn, pn = t.asnumpy(), p.asnumpy()
            rtol = 64 * onp.float64(jnp.finfo(tn.dtype).eps) \
                if jnp.issubdtype(tn.dtype, jnp.floating) else 0
            if tn.shape != pn.shape or not onp.allclose(
                    tn.astype(onp.float64), pn.astype(onp.float64),
                    rtol=rtol, atol=0, equal_nan=True):
                return ("padded loss differs from unpadded — the loss is "
                        "not pad-safe (mask pad rows, e.g. with the "
                        "DataLoader last_batch='pad' valid count, or use "
                        "a sum-style masked reduction)")
        return None

    # -- eligibility / fallback ------------------------------------------
    def _eligibility(self) -> Optional[str]:
        from .optimizer import fused as _fused

        tr = self._trainer
        if not enabled():
            return "MXNET_COMPILED_STEP=0"
        if self.fallback_reason is not None:
            return self.fallback_reason
        if not _fused.supports(tr._optimizer):
            return (f"optimizer {type(tr._optimizer).__name__} has no "
                    "functional fused_update rule")
        if tr._update_on_kvstore:
            return "update_on_kvstore=True applies updates server-side"
        mesh = self._resolve_mesh()
        if tr._kvstore is not None and tr._kvstore.num_workers > 1 \
                and mesh is None:
            return (f"multi-worker '{tr._kvstore.type}' kvstore reduction "
                    "is host-driven (dist/ps-lite); the staged SPMD "
                    "all-reduce covers kvstore='tpu' (pod-scale SPMD "
                    "training, ISSUE 6)")
        for p in tr._params:
            if p.grad_req == "add":
                return f"parameter '{p.name}' has grad_req='add'"
        for p in self._net.collect_params().values():
            if p._data is None:
                return ("deferred parameter init pending (first call "
                        "runs eagerly, like hybridize)")
            if len(p._data) > 1:
                return "multi-replica (multi-ctx) parameters"
        return None

    def _eager_step(self, args, batch_size):
        """The eager tape path, AMP-equivalent to amp.scale_loss +
        backward + trainer.step."""
        if self._accum_steps > 1:
            # the eager tape applies one update PER call — silently
            # turning an N-microbatch window into N full steps would
            # change lr/count semantics, so accumulation refuses the
            # tape loudly instead of degrading wrong
            from .base import MXNetError

            raise MXNetError(
                f"accum_steps={self._accum_steps} requires the compiled "
                "step (one fused update per window); the eager tape "
                "cannot honor the window contract — fallback reason: "
                f"{self.last_fallback_reason}")
        # a pending deferred flag must land first: the eager step reads
        # scaler.loss_scale synchronously, so the host state has to be
        # caught up to the device before this step's scale is chosen
        self.drain()
        tr = self._trainer
        if self._mesh is not None and self._params_on_mesh():
            # a sticky fallback AFTER mesh placement: the parameters
            # already live replicated across the mesh, and eager ops
            # require colocated operands — stage the batch replicated too
            from .parallel import spmd as _spmd

            rep = _spmd.replicated(self._mesh)

            def _rep(a):
                if isinstance(a, (tuple, list)):
                    return type(a)(_rep(v) for v in a)
                if hasattr(a, "_data"):
                    from .ndarray import ndarray as _nd

                    return _nd._wrap(jax.device_put(a._data, rep),
                                     a.ctx, type(a))
                return a
            args = tuple(_rep(a) for a in args)
        scaler = getattr(tr, "_amp_loss_scaler", None)
        from .parallel import moe as _moe
        with autograd.record():
            with _moe.aux_scope() as auxes:
                loss = self._loss_fn(self._net, *args)
            heads = list(loss) if isinstance(loss, (list, tuple)) else [loss]
            if auxes:
                # MoE load-balance loss: same extra differentiated head
                # the compiled program folds, so eager == compiled
                aux_w = float(_config.get("MXNET_MOE_AUX_WEIGHT"))
                at = auxes[0]
                for a in auxes[1:]:
                    at = at + a
                heads = heads + [at * aux_w]
            if scaler is not None and scaler.loss_scale != 1.0:
                heads = [h * scaler.loss_scale for h in heads]
        autograd.backward(heads)
        gt = getattr(self._net, "compiled_grad_transform", None)
        if gt is not None:
            named = {}
            for n, p in self._net.collect_params().items():
                if p.grad_req != "null":
                    named[n] = p.grad()._data
            for n, g in gt(dict(named)).items():
                if named.get(n) is not g:
                    self._net.collect_params()[n].grad()._set_data(g)
        if scaler is not None:
            base = getattr(tr, "_amp_original_scale", tr._scale)
            tr._amp_original_scale = base
            tr._scale = base / scaler.loss_scale
        tr.step(batch_size)
        return loss

    # -- the compiled step ------------------------------------------------
    def _prep(self):
        """State-side preparation shared by dispatch and
        :meth:`precompile`: parameter/optimizer-state layout, update
        groups, and (under a mesh) the one-time replicated placement.
        Depends only on trainer/net state, never on the input batch."""
        from types import SimpleNamespace

        from .optimizer import fused as _fused

        tr = self._trainer
        opt = tr._optimizer
        scaler = getattr(tr, "_amp_loss_scaler", None)
        updater = tr._updaters[0]

        params = OrderedDict(
            (n, p) for n, p in self._net.collect_params().items()
            if p._data is not None)
        names = list(params)
        # trainable set/order follows trainer._params — the order the
        # eager fused path groups and checks finiteness in
        trainable = [p for p in tr._params if p.grad_req != "null"]
        indices = [tr._param2idx[id(p)] for p in trainable]
        # gradients live only inside the program: a Parameter's grad
        # buffer (single-device zeros on the first device) would be a
        # second model there that nothing reads; the eager tape
        # re-creates it on its next backward.  Released BEFORE the
        # optimizer state is created, or weights, gradients and state are
        # all alive at once and that moment is the process's peak
        for p in trainable:
            if p._grad is not None:
                p._release_grad()
        for p, idx in zip(trainable, indices):
            if idx not in updater.states:
                updater.states[idx] = opt.create_state_multi_precision(
                    idx, p.data())
                updater.states_synced[idx] = True
        states = [updater.states[idx] for idx in indices]
        mps = [_fused._is_mp_state(opt, p.data(), s)
               for p, s in zip(trainable, states)]
        groups: "OrderedDict" = OrderedDict()
        for i, p in enumerate(trainable):
            groups.setdefault((p.data()._data.dtype, mps[i]), []).append(i)
        group_layout = tuple((mp, tuple(m))
                             for (_dt, mp), m in groups.items())

        slot_of_name: Dict[str, int] = {}
        trainable_ids = {id(p): i for i, p in enumerate(trainable)}
        for n in names:
            i = trainable_ids.get(id(params[n]))
            if i is not None:
                slot_of_name[n] = i
        frozen_names = [n for n in names if n not in slot_of_name]

        from .parallel import spmd as _spmd

        mesh = self._mesh
        if mesh is not None:
            rep = _spmd.replicated(mesh)
            model_axes = _spmd.model_axes_active(mesh)
        else:
            # one chip is the one-device case of the same code: every
            # operand is placed on the step's device (the parameters'
            # context) explicitly, so a host-committed array is
            # re-placed (counted in spmd.reshard) instead of deciding
            # where the program runs
            owner = trainable[0] if trainable else next(
                iter(params.values()), None)
            ctx = owner.data().ctx if owner is not None \
                else current_context()
            rep = jax.sharding.SingleDeviceSharding(ctx.jax_device)
            model_axes = False
        name_of = {id(p): n for n, p in params.items()}

        def _sharding_of(shape, pname=None):
            # any model axis present (fsdp/pp/ep): per-leaf
            # name+shape-aware placement — pp packed stage buffers
            # and ep expert weights by NAME, then the ZeRO rule
            # (largest divisible dim, small/indivisible leaves
            # replicate — the latter loudly); otherwise the classic
            # replicated KVStore-broadcast layout
            if model_axes:
                return _spmd.param_sharding(tuple(shape), mesh,
                                            name=pname)
            return rep

        def _place_nd(d, sh=None):
            new = _spmd.ensure_placed(
                d._data, sh if sh is not None else rep)
            if new is not d._data:
                d._set_data(new)

        def _place_state(s, wshape, wsh):
            # optimizer-state leaves SHAPED like their weight
            # (momentum, Adam moments, the fp32 master copy) shard
            # with it — that is the ZeRO part of FSDP; scalars and
            # odd-shaped leaves replicate
            if s is None:
                return
            if hasattr(s, "_set_data"):
                same = tuple(s.shape) == tuple(wshape)
                _place_nd(s, wsh if same else rep)
                return
            for x in s:
                _place_state(x, wshape, wsh)

        # one-time placement (the KVStore init/broadcast analog):
        # steady state sees already-placed buffers — the step's
        # outputs carry the same shardings back into the
        # parameters, so reshard_count stays flat after warmup
        for p in trainable:
            _place_nd(p.data(), _sharding_of(p.data().shape,
                                             name_of.get(id(p))))
        for n in frozen_names:
            _place_nd(params[n].data(),
                      _sharding_of(params[n].data().shape, n))
        for p, s in zip(trainable, states):
            _place_state(s, p.data().shape,
                         _sharding_of(p.data().shape,
                                      name_of.get(id(p))))

        if mesh is not None:
            # per-device memory accounting (gauges
            # spmd.param_bytes_per_device / spmd.opt_bytes_per_device):
            # computed from the placed leaves' ACTUAL shardings, so the
            # fsdp layout reads ~1/N of the replicated one
            _spmd.record_layout(
                [p.data()._data for p in trainable]
                + [params[n].data()._data for n in frozen_names],
                [l for s in states
                 for l in jax.tree_util.tree_leaves(_fused._unwrap(s))])

        # donation aliases the old weight/optimizer-state HBM into the
        # outputs — the whole point of the fused step on chip; decided
        # by the platform the step RUNS on, not the process default
        platform = next(iter(rep.device_set)).platform
        return SimpleNamespace(
            opt=opt, scaler=scaler, updater=updater, params=params,
            names=names, trainable=trainable, indices=indices,
            states=states, group_layout=group_layout,
            slot_of_name=slot_of_name, frozen_names=frozen_names,
            mesh=mesh, rep=rep, has_ok=scaler is not None,
            donate=platform != "cpu")

    def _signature(self, prep, in_struct_key, in_specs, ctx, flavor):
        """The program-cache key: input structure + shapes/dtypes ×
        train-mode × hyper-param signature × parameter/state layout ×
        mesh — ``in_specs`` is ``tuple((shape, dtype), ...)`` so real
        leaves and abstract precompile specs key identically."""
        from .ndarray import ndarray as _ndmod
        from .optimizer import fused as _fused

        mesh = prep.mesh
        if mesh is not None:
            from .parallel import spmd as _spmd
        return (
            in_struct_key,
            tuple(in_specs),
            True,                       # train-mode (part of the key by
            _ndmod._amp_generation,     # contract; TrainStep trains)
            ctx, flavor,
            type(prep.opt).__name__, prep.opt._fused_signature(),
            tuple((tuple(p.data().shape), p.data()._data.dtype)
                  for p in prep.trainable),
            tuple(_fused._struct(s) for s in prep.states),
            tuple((n, tuple(prep.params[n].data().shape),
                   prep.params[n].data()._data.dtype)
                  for n in prep.frozen_names),
            prep.group_layout, prep.has_ok, prep.donate,
            # the SPMD mesh (axes + exact device set): a topology change
            # must never reuse a program compiled for another
            None if mesh is None else _spmd.mesh_key(mesh),
        )

    def _mesh_ctx(self, mesh):
        """The mesh context the step traces AND dispatches under: inside
        it ``sharding.constraint`` calls in model code resolve the
        ``'tp'``/``'fsdp'`` axis names without the mesh threaded through
        the call stack (single-chip: a no-op context)."""
        if mesh is None:
            import contextlib

            return contextlib.nullcontext()
        from .parallel.mesh import mesh_scope

        return mesh_scope(mesh)

    def _ensure_program(self, sig, prep, in_struct, ctx, flavor,
                        lower_args, kind="full"):
        """One code path for warm-up, steady state, and elastic restore:
        resolve ``sig`` through the ProgramStore — a miss traces AND
        AOT-compiles (persisting to MXNET_PROGRAM_CACHE_DIR when set)
        before any dispatch.  ``kind`` selects the program body: the
        whole fused step (``'full'``), the accumulation-window grad
        program (``'grad'``), or the window-closing update program
        (``'update'``).  Tracing happens inside the mesh context so
        model-code sharding constraints resolve."""
        rec = self._programs.lookup(sig)
        if rec is None:
            with self._mesh_ctx(prep.mesh):
                if kind == "full":
                    jitted, out_struct, mutated_names = \
                        self._build_program(prep, in_struct, ctx, flavor)
                elif kind == "grad":
                    jitted, out_struct, mutated_names = \
                        self._build_grad_program(prep, in_struct, ctx,
                                                 flavor)
                else:
                    jitted = self._build_update_program(prep)
                    out_struct, mutated_names = None, ()
                rec = _pstore.build(
                    "train_step", jitted, lower_args,
                    meta=(out_struct, mutated_names),
                    label=type(self._net).__name__,
                    module="jit_" + jitted.__name__)    # _named
            self._programs.insert(sig, rec)
        return rec

    def precompile(self, *specs, batch_size=None):
        """Ahead-of-time compilation of the train step from abstract
        input shapes, BEFORE the first batch arrives (deploy-time /
        elastic-restore warm-up; `Trainer.precompile` wraps this).

        ``specs`` are the step's positional inputs, each either a real
        NDArray example or a ``(shape, dtype)`` pair.  The program is
        traced and XLA-compiled through the ProgramStore exactly as the
        first dispatch would — with ``MXNET_PROGRAM_CACHE_DIR`` set the
        executable also lands in the persistent cache, so a later
        process skips the compile entirely.  No data is touched, no
        step runs, no parameter/optimizer state changes (under a mesh,
        parameters take their one-time replicated placement, exactly as
        the first step would).  Raises when the step would fall back to
        the eager tape (a silent warm-up of nothing helps no one).
        Returns ``self`` so ``trainer.precompile(...)`` chains."""
        import numpy as onp

        from .base import MXNetError
        from .gluon import block as _gb
        from .ndarray import ndarray as _ndmod

        tr = self._trainer
        if not tr._kv_initialized:
            tr._init_kvstore()
        if tr._params_to_init:
            tr._init_params()
        reason = self._eligibility()
        if reason is not None:
            raise MXNetError(
                f"precompile: the compiled step would fall back to the "
                f"eager tape ({reason})")
        nd_specs = [s for s in specs if hasattr(s, "_data")]
        if nd_specs and len(nd_specs) == len(specs):
            in_leaves, in_struct = _gb._flatten_args(tuple(specs))
            shapes = [tuple(l.shape) for l in in_leaves]
            dtypes = [l._data.dtype for l in in_leaves]
            ctx = in_leaves[0].ctx if in_leaves else current_context()
            flavor = _ndmod._flavor_of(in_leaves)
        else:
            shapes, dtypes = [], []
            for s in specs:
                shape, dtype = s
                shapes.append(tuple(int(d) for d in shape))
                dtypes.append(onp.dtype(dtype))
            # flat positional args: the same treedef _flatten_args
            # produces for step(x, y, ...)
            in_struct = [("_leaf_", i) for i in range(len(specs))]
            ctx = current_context()
            flavor = _ndmod._flavor_of([])
        if self._bucket and self.bucket_refused is None and shapes:
            # precompile the PADDED program the bucketed step dispatches
            from . import serving as _serving

            policy = _serving.BucketPolicy()
            if policy.enabled:
                n = shapes[0][0]
                b = policy.bucket(n)
                if b is not None and b != n:
                    shapes = [(b,) + s[1:] if s and s[0] == n else s
                              for s in shapes]

        prep = self._prep()
        sig = self._signature(
            prep, _gb._struct_key(in_struct),
            tuple((s, d) for s, d in zip(shapes, dtypes)), ctx, flavor)
        in_sds = [jax.ShapeDtypeStruct(s, d)
                  for s, d in zip(shapes, dtypes)]
        if self._accum_steps > 1:
            # the accumulation window runs TWO programs: warm both
            usig = self._update_sig(prep, ctx, flavor)
            self._ensure_accum_bufs(prep, usig)
            self._ensure_program(
                ("accum_grad", self._accum_steps) + sig, prep, in_struct,
                ctx, flavor, self._grad_lower_args(prep, in_sds),
                kind="grad")
            self._ensure_program(
                usig, prep, None, ctx, flavor,
                self._update_lower_args(prep), kind="update")
        else:
            self._ensure_program(sig, prep, in_struct, ctx, flavor,
                                 self._lower_args(prep, in_sds))
        return self

    def _lower_args(self, prep, in_specs):
        """Abstract lowering arguments matching the dispatch call
        signature: real parameter/state buffers (their avals ARE the
        program's), ShapeDtypeStructs for the batch (mesh-sharded like
        ``spmd.put_batch`` would shard the real batch), abstract
        scalars for the per-step traced values."""
        f32 = jax.ShapeDtypeStruct((), jnp.float32)
        mesh = prep.mesh
        if mesh is not None:
            from .parallel import spmd as _spmd

            # batch divisibility follows the 'dp' axis size ONLY — on a
            # multi-axis mesh (dp×fsdp/tp) the whole-mesh device count
            # is NOT the batch-sharding divisor (matching batch_spec_for,
            # so the precompiled program equals the dispatched one)
            n_dp = int(mesh.shape.get(_spmd.DATA_AXIS, 1))
            bsh = _spmd.batch_sharding(mesh)

            def _in_spec(s):
                sh = bsh if (s.shape and s.shape[0] % n_dp == 0) \
                    else prep.rep
                return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

            in_specs = [_in_spec(s) for s in in_specs]
            prev_ok = jax.ShapeDtypeStruct((), jnp.bool_,
                                           sharding=prep.rep)
            want_dig = jax.ShapeDtypeStruct((), jnp.bool_,
                                            sharding=prep.rep)
        else:
            prev_ok = jax.ShapeDtypeStruct((), jnp.bool_)
            want_dig = jax.ShapeDtypeStruct((), jnp.bool_)
        g32 = [jax.ShapeDtypeStruct((len(m),), jnp.float32)
               for _mp, m in prep.group_layout]
        from .optimizer import fused as _fused

        w_args = [p.data()._data for p in prep.trainable]
        s_args = tuple(_fused._unwrap(s) for s in prep.states)
        frozen_args = [prep.params[n].data()._data
                       for n in prep.frozen_names]
        return (w_args, s_args, frozen_args, list(in_specs),
                jax.random.PRNGKey(0), list(g32), list(g32), list(g32),
                f32, f32, f32, f32, prev_ok, want_dig)

    def _compiled_step(self, args, batch_size, ph):
        from .gluon import block as _gb
        from .ndarray import ndarray as _ndmod
        from .optimizer import fused as _fused

        if self._accum_steps > 1:
            return self._accum_compiled_step(args, batch_size, ph)
        tr = self._trainer
        in_leaves, in_struct = _gb._flatten_args(args)
        ctx = in_leaves[0].ctx if in_leaves else current_context()
        flavor = _ndmod._flavor_of(in_leaves)

        prep = self._prep()
        opt, scaler = prep.opt, prep.scaler
        indices, group_layout = prep.indices, prep.group_layout
        trainable, states = prep.trainable, prep.states
        mesh, rep = prep.mesh, prep.rep
        sig = self._signature(
            prep, _gb._struct_key(in_struct),
            tuple((tuple(l.shape), l._data.dtype) for l in in_leaves),
            ctx, flavor)

        # per-step traced values: counts were bumped by __call__ already
        counts = [opt._index_update_count[i] for i in indices]
        lrs = opt._get_lrs(list(indices))
        wds = opt._get_wds(list(indices))
        # deferred AMP gate (MXNET_AMP_LAG): while a previous step's
        # all-finite flag is unread, this step dispatches speculatively
        # with BOTH scale candidates — the clean-branch scale and the
        # overflow-branch scale, each computed by the SAME host policy
        # the synchronous gate runs — and the program selects on the
        # device flag.  Numerics are bit-exact vs the synchronous gate
        # because the selected candidate IS the value sync would pass.
        lag = _engine.amp_lag() if scaler is not None else 0
        if not lag:
            self.drain()          # lag window closed: catch up first
        if scaler is not None and lag and self._pending_ok is not None:
            s_clean, s_over = scaler.branch_scales()
        elif scaler is not None:
            s_clean = s_over = scaler.loss_scale
        else:
            s_clean = s_over = 1.0
        scale_val = s_clean
        if scaler is not None:
            tr._amp_original_scale = getattr(
                tr, "_amp_original_scale", tr._scale)
        base = getattr(tr, "_amp_original_scale", tr._scale)
        rescale = base / (scale_val * batch_size)
        rescale_alt = base / (s_over * batch_size)
        # from here every line makes or reads a device value: the small
        # programs beside the step are launched in this phase
        ph.to("train_step.operands")
        if self._pending_ok is not None:
            prev_ok = self._pending_ok
        elif mesh is not None:
            # pin the seed flag to the mesh so the first deferred step
            # traces with the same (replicated) sharding later flags
            # carry — otherwise step 2 would pay a one-off retrace
            prev_ok = jax.device_put(jnp.asarray(True), rep)
        else:
            prev_ok = jnp.asarray(True)
        lrs_g = [jnp.asarray([lrs[i] for i in m], jnp.float32)
                 for _mp, m in group_layout]
        wds_g = [jnp.asarray([wds[i] for i in m], jnp.float32)
                 for _mp, m in group_layout]
        counts_g = [jnp.asarray([counts[i] for i in m], jnp.float32)
                    for _mp, m in group_layout]

        w_args = [p.data()._data for p in trainable]
        s_args = tuple(_fused._unwrap(s) for s in states)
        frozen_args = [prep.params[n].data()._data
                       for n in prep.frozen_names]
        from .parallel import spmd as _spmd

        if mesh is not None:
            # batch leaves shard over 'dp' (legalized: an indivisible
            # batch axis replicates, loudly).  Leaves the prefetcher
            # already staged with this sharding pass through untouched.
            in_args = [_spmd.put_batch(l._data, mesh) for l in in_leaves]
        else:
            in_args = [_spmd.ensure_placed(l._data, rep)
                       for l in in_leaves]

        # sentinel cadence: the traced want_digest flag selects the
        # in-program lax.cond digest branch — value changes never
        # retrace, and under a mesh the flag pins replicated exactly
        # like the seed AMP flag above
        snt = self._sentinel
        want_digest = snt is not None and snt.want_digest()
        if mesh is not None:
            want_arg = jax.device_put(jnp.asarray(want_digest), rep)
        else:
            want_arg = jnp.asarray(want_digest)
        call_args = (
            w_args, s_args, frozen_args, in_args, _random.next_key(),
            lrs_g, wds_g, counts_g,
            jnp.asarray(rescale, jnp.float32),
            jnp.asarray(scale_val, jnp.float32),
            jnp.asarray(s_over, jnp.float32),
            jnp.asarray(rescale_alt, jnp.float32),
            prev_ok, want_arg)
        ph.to("train_step.launch", program="step")
        rec = self._ensure_program(sig, prep, in_struct, ctx, flavor,
                                   call_args)
        out_struct, mutated_names = rec.meta
        with self._mesh_ctx(mesh):
            out_raw, mut_vals, new_w, new_s, ok, dig = rec(*call_args)
        ph.to("train_step.writeback")
        if want_digest:
            # hand the UNREAD device fingerprint to the sentinel; it
            # consumes the previous pending one (deferred a full
            # cadence — that program retired long ago, so the read
            # rides the PR-5 lag machinery, never a stall on this step)
            snt.offer(*dig)

        for p, nw in zip(trainable, new_w):
            p._data[0]._set_data(nw)
        for s, ns in zip(states, new_s):
            _fused._write(s, ns)
        # mutation (BN running stats) writes LAST: a forward mutating a
        # TRAINABLE param cannot be expressed in one program — its
        # mutation wins this step and the step goes sticky-eager
        for n, v in zip(mutated_names, mut_vals):
            prep.params[n]._data[0]._set_data(v)
        overlap = [n for n in mutated_names if n in prep.slot_of_name]
        if overlap:
            self.fallback_reason = (
                f"forward mutates trainable parameter(s) {overlap}")
        out_nd = [_ndmod._wrap(o, ctx, flavor) for o in out_raw]
        loss = _gb._rebuild_output(out_struct[0], out_nd)
        if scaler is not None:
            ph.to("train_step.gate", where="deferred" if lag else "sync")
            if lag:
                # deferred gate: hold THIS step's flag, read the
                # PREVIOUS one (already materialized — its program
                # finished while this step was being prepared, so the
                # read is lagged, never a stall on the current program)
                prev = self._pending_ok
                self._pending_ok = ok
                if prev is not None:
                    _ndmod.count_host_sync()
                    _DEFERRED_READ.inc()
                    # graftlint: disable=host-sync -- the ONE deferred AMP
                    # gate read per step (lagged: never blocks the current
                    # program), counted via count_host_sync
                    overflow = not bool(prev)
                    if overflow:
                        _telemetry.event("amp_overflow", "cached_step",
                                         where="deferred")
                    scaler.update_scale(overflow)
            else:
                # the ONE host read of the step: the device all-finite
                # flag drives the loss-scale policy synchronously
                _ndmod.count_host_sync()
                # graftlint: disable=host-sync -- the documented synchronous
                # AMP gate read (MXNET_AMP_LAG=0 / NaiveEngine), counted
                overflow = not bool(ok)
                if overflow:
                    _telemetry.event("amp_overflow", "cached_step",
                                     where="sync")
                scaler.update_scale(overflow)
        return loss

    # -- gradient accumulation (compile_step(accum_steps=N)) --------------
    def _update_sig(self, prep, ctx, flavor):
        """The window-closing update program's cache key: it never sees
        the batch, so input structure/shapes are deliberately absent —
        alternating microbatch shapes share ONE update program (and one
        set of accumulator buffers)."""
        from .ndarray import ndarray as _ndmod
        from .optimizer import fused as _fused

        mesh = prep.mesh
        if mesh is not None:
            from .parallel import spmd as _spmd
        return (
            "accum_update", self._accum_steps, ctx, flavor,
            _ndmod._amp_generation,
            type(prep.opt).__name__, prep.opt._fused_signature(),
            tuple((tuple(p.data().shape), p.data()._data.dtype)
                  for p in prep.trainable),
            tuple(_fused._struct(s) for s in prep.states),
            prep.group_layout, prep.has_ok, prep.donate,
            None if mesh is None else _spmd.mesh_key(mesh),
        )

    def _ensure_accum_bufs(self, prep, key) -> None:
        """Donation-safe gradient accumulators: one zeros buffer per
        trainable param, placed with the SAME sharding (fsdp-sharded
        grads accumulate shard-local, no gather).  Built once per
        (param-layout, mesh) signature; the update program returns
        freshly ZEROED buffers in the donated slots, so steady state
        never pays an eager zeros dispatch."""
        if self._accum_bufs is not None and self._accum_key == key:
            return
        bufs = []
        for p in prep.trainable:
            w = p.data()._data
            bufs.append(jax.device_put(jnp.zeros(w.shape, w.dtype),
                                       w.sharding))
        self._accum_bufs = bufs
        self._accum_key = key
        self._accum_i = 0

    def _accum_compiled_step(self, args, batch_size, ph):
        """One microbatch of an accumulation window: dispatch the grad
        program (adds this microbatch's scaled grads into the donated
        accumulators); the window-FINAL microbatch also dispatches the
        fused update program — exactly ``accum_steps + 1`` dispatches
        and ONE optimizer update (one count bump, one lr read) per
        window.  The AMP gate spans the window: the loss scale holds
        fixed across it (the deferred flag lands only at window close)
        and overflow is detected on the SUMMED grads — an inf/nan from
        any microbatch survives addition."""
        from .gluon import block as _gb
        from .ndarray import ndarray as _ndmod
        from .optimizer import fused as _fused

        tr = self._trainer
        accum = self._accum_steps
        in_leaves, in_struct = _gb._flatten_args(args)
        ctx = in_leaves[0].ctx if in_leaves else current_context()
        flavor = _ndmod._flavor_of(in_leaves)

        prep = self._prep()
        opt, scaler = prep.opt, prep.scaler
        mesh, rep = prep.mesh, prep.rep
        base_sig = self._signature(
            prep, _gb._struct_key(in_struct),
            tuple((tuple(l.shape), l._data.dtype) for l in in_leaves),
            ctx, flavor)
        gsig = ("accum_grad", accum) + base_sig
        usig = self._update_sig(prep, ctx, flavor)
        self._ensure_accum_bufs(prep, usig)

        # the window's scale candidates: every microbatch passes the
        # same (clean, overflow) pair and the same unread previous
        # flag, so the on-device where() selects ONE scale for the
        # whole window and the summed grads equal a big-batch
        # backward's, scaled.  (A mid-window drain() is safe: it
        # resolves the flag to exactly the value the where() selects.)
        lag = _engine.amp_lag() if scaler is not None else 0
        if not lag:
            self.drain()
        if scaler is not None and lag and self._pending_ok is not None:
            s_clean, s_over = scaler.branch_scales()
        elif scaler is not None:
            s_clean = s_over = scaler.loss_scale
        else:
            s_clean = s_over = 1.0
        ph.to("train_step.operands")
        if self._pending_ok is not None:
            prev_ok = self._pending_ok
        elif mesh is not None:
            prev_ok = jax.device_put(jnp.asarray(True), rep)
        else:
            prev_ok = jnp.asarray(True)

        w_args = [p.data()._data for p in prep.trainable]
        frozen_args = [prep.params[n].data()._data
                       for n in prep.frozen_names]
        from .parallel import spmd as _spmd

        if mesh is not None:
            in_args = [_spmd.put_batch(l._data, mesh) for l in in_leaves]
        else:
            in_args = [_spmd.ensure_placed(l._data, rep)
                       for l in in_leaves]
        g_call = (w_args, frozen_args, list(self._accum_bufs), in_args,
                  _random.next_key(),
                  jnp.asarray(s_clean, jnp.float32),
                  jnp.asarray(s_over, jnp.float32), prev_ok)
        ph.to("train_step.launch", program="grad")
        grec = self._ensure_program(gsig, prep, in_struct, ctx, flavor,
                                    g_call, kind="grad")
        out_struct, mutated_names = grec.meta
        with self._mesh_ctx(mesh):
            out_raw, mut_vals, new_acc = grec(*g_call)
        ph.to("train_step.writeback")
        self._accum_bufs = list(new_acc)
        for n, v in zip(mutated_names, mut_vals):
            prep.params[n]._data[0]._set_data(v)
        overlap = [n for n in mutated_names if n in prep.slot_of_name]
        if overlap:
            self.fallback_reason = (
                f"forward mutates trainable parameter(s) {overlap}")
        out_nd = [_ndmod._wrap(o, ctx, flavor) for o in out_raw]
        loss = _gb._rebuild_output(out_struct[0], out_nd)

        self._accum_i += 1
        if self._accum_i < accum:
            return loss
        self._accum_i = 0

        # ---- window close: the ONE fused update dispatch ---------------
        # (the same phases a second time: this call has two launches)
        ph.to("train_step.prep")
        indices, group_layout = prep.indices, prep.group_layout
        counts = [opt._index_update_count[i] for i in indices]
        lrs = opt._get_lrs(list(indices))
        wds = opt._get_wds(list(indices))
        scale_val = s_clean
        if scaler is not None:
            tr._amp_original_scale = getattr(
                tr, "_amp_original_scale", tr._scale)
        base = getattr(tr, "_amp_original_scale", tr._scale)
        # the accumulators hold a SUM over accum microbatches of scaled
        # per-microbatch-mean grads; the extra /accum makes the window
        # equal one (accum × batch_size)-batch step's mean
        rescale = base / (scale_val * batch_size * accum)
        rescale_alt = base / (s_over * batch_size * accum)
        ph.to("train_step.operands")
        lrs_g = [jnp.asarray([lrs[i] for i in m], jnp.float32)
                 for _mp, m in group_layout]
        wds_g = [jnp.asarray([wds[i] for i in m], jnp.float32)
                 for _mp, m in group_layout]
        counts_g = [jnp.asarray([counts[i] for i in m], jnp.float32)
                    for _mp, m in group_layout]
        s_args = tuple(_fused._unwrap(s) for s in prep.states)
        snt = self._sentinel
        want_digest = snt is not None and snt.want_digest()
        if mesh is not None:
            want_arg = jax.device_put(jnp.asarray(want_digest), rep)
        else:
            want_arg = jnp.asarray(want_digest)
        u_call = (w_args, s_args, list(self._accum_bufs),
                  lrs_g, wds_g, counts_g,
                  jnp.asarray(rescale, jnp.float32),
                  jnp.asarray(rescale_alt, jnp.float32),
                  prev_ok, want_arg)
        ph.to("train_step.launch", program="update")
        urec = self._ensure_program(usig, prep, None, ctx, flavor,
                                    u_call, kind="update")
        with self._mesh_ctx(mesh):
            new_w, new_s, new_acc, ok, dig = urec(*u_call)
        ph.to("train_step.writeback")
        self._accum_bufs = list(new_acc)
        if want_digest:
            snt.offer(*dig)
        for p, nw in zip(prep.trainable, new_w):
            p._data[0]._set_data(nw)
        for s, ns in zip(prep.states, new_s):
            _fused._write(s, ns)
        if scaler is not None:
            ph.to("train_step.gate", where="deferred" if lag else "sync")
            if lag:
                prev = self._pending_ok
                self._pending_ok = ok
                if prev is not None:
                    _ndmod.count_host_sync()
                    _DEFERRED_READ.inc()
                    # graftlint: disable=host-sync -- the ONE deferred AMP
                    # gate read per window (lagged: never blocks the
                    # current program), counted via count_host_sync
                    overflow = not bool(prev)
                    if overflow:
                        _telemetry.event("amp_overflow", "cached_step",
                                         where="deferred")
                    scaler.update_scale(overflow)
            else:
                _ndmod.count_host_sync()
                # graftlint: disable=host-sync -- the synchronous AMP gate
                # read at window close (MXNET_AMP_LAG=0), counted
                overflow = not bool(ok)
                if overflow:
                    _telemetry.event("amp_overflow", "cached_step",
                                     where="sync")
                scaler.update_scale(overflow)
        return loss

    @staticmethod
    def _pinned(prep):
        """``(weights, optimizer state)`` output shardings = the
        placements :meth:`_prep` just gave the inputs.  Left to the
        partitioner, a small replicated leaf can come back sharded over
        ``fsdp`` (or the reverse): ``_prep`` would then re-place it
        EVERY step (a steady-state reshard) and its donated buffer
        could not alias."""
        from .optimizer import fused as _fused

        def sh(tree):
            return jax.tree_util.tree_map(lambda a: a.sharding, tree)

        return (sh([p.data()._data for p in prep.trainable]),
                tuple(sh(_fused._unwrap(s)) for s in prep.states))

    @staticmethod
    def _pin_mutations(prep, mutated_names, muts):
        """Same pin for forward-mutated parameters (BN running stats).
        Their names are only known once the forward has traced, so the
        pin is a constraint inside the program, not an ``out_shardings``
        entry."""
        if prep.mesh is None:
            return muts
        return [jax.lax.with_sharding_constraint(
                    m, prep.params[n].data()._data.sharding)
                for n, m in zip(mutated_names, muts)]

    def _grad_hook(self, slot_of_name):
        """The net-level compiled gradient hook: a net exposing
        ``compiled_grad_transform(named_grads) -> named_grads`` (e.g.
        ``parallel.pipeline.PipelineBlock`` summing tied embed/head
        slices on the packed cotangent) gets it applied INSIDE the
        compiled program, right after the vjp, on both the full-step and
        the accumulation microbatch programs.  Returns ``(slot_names,
        transform)`` — ``(None, None)`` when the net has no hook."""
        gt = getattr(self._net, "compiled_grad_transform", None)
        if gt is None:
            return None, None
        n_slots = (max(slot_of_name.values()) + 1) if slot_of_name else 0
        slot_names: List[Optional[str]] = [None] * n_slots
        for n, i in slot_of_name.items():
            slot_names[i] = n
        return slot_names, gt

    @staticmethod
    def _apply_grad_transform(slot_names, gt, grads):
        if gt is None:
            return grads
        names = list(slot_names) + [None] * (len(grads) - len(slot_names))
        named = {n: g for n, g in zip(names, grads) if n is not None}
        named = gt(named)
        return [named.get(n, g) if n is not None else g
                for n, g in zip(names, grads)]

    @staticmethod
    def _fold_aux(auxes, heads, scale_eff, has_ok):
        """Fold recorded MoE load-balance aux losses into the
        differentiated heads as ONE extra (scaled) head — seeded with a
        unit cotangent like every head, so ``aux_weight * d(aux)``
        reaches the grads/optimizer while the user-visible loss outputs
        stay untouched."""
        if not auxes:
            return heads
        aux_w = float(_config.get("MXNET_MOE_AUX_WEIGHT"))
        at = auxes[0]
        for a in auxes[1:]:
            at = at + a
        at = (at * aux_w).astype(jnp.float32)
        return list(heads) + [at * scale_eff if has_ok else at]

    def _build_grad_program(self, prep, in_struct, ctx, flavor):
        """The accumulation-window microbatch program: forward + vjp
        only, adding this microbatch's (scaled) grads into the DONATED
        accumulator buffers — no optimizer math, no state touched."""
        params, names = prep.params, prep.names
        slot_of_name, frozen_names = prep.slot_of_name, prep.frozen_names
        has_ok, donate = prep.has_ok, prep.donate
        from .gluon import block as _gb

        from .parallel import moe as _moe

        net, loss_fn = self._net, self._loss_fn
        raw_fwd, out_struct, mutated_names = _gb._stage_fn(
            lambda *call_args: loss_fn(net, *call_args),
            params, names, in_struct, True, ctx, flavor)
        frozen_pos = {n: j for j, n in enumerate(frozen_names)}
        slot_names, gtrans = self._grad_hook(slot_of_name)

        def grad_fn(w_list, frozen_list, acc_list, in_list, rng_key,
                    scale, scale_alt, prev_ok):
            _pstore.count_trace("train_step")
            if has_ok:
                with jax.named_scope("loss_scale"):
                    scale_eff = jnp.where(prev_ok, scale, scale_alt)
            else:
                scale_eff = scale

            def fwd(w_l):
                full = [w_l[slot_of_name[n]] if n in slot_of_name
                        else frozen_list[frozen_pos[n]] for n in names]
                with _moe.aux_scope() as auxes:
                    outs, muts = raw_fwd(full, in_list, rng_key)
                with jax.named_scope("loss_scale"):
                    heads = [o * scale_eff for o in outs] if has_ok \
                        else list(outs)
                    heads = self._fold_aux(auxes, heads, scale_eff,
                                           has_ok)
                return heads, (outs, muts)

            heads, vjp_fn, (outs, muts) = jax.vjp(
                fwd, list(w_list), has_aux=True)
            cts = [jnp.ones(h.shape, h.dtype) for h in heads]
            (grads,) = vjp_fn(cts)
            with jax.named_scope("grad_finite_check"):
                grads = [g.astype(w.dtype) if g.dtype != w.dtype else g
                         for g, w in zip(grads, w_list)]
            grads = self._apply_grad_transform(slot_names, gtrans, grads)
            with jax.named_scope("grad_accumulate"):
                new_acc = [a + g for a, g in zip(acc_list, grads)]
            return (outs, self._pin_mutations(prep, mutated_names, muts),
                    new_acc)

        w_sh, _s_sh = self._pinned(prep)
        jitted = jax.jit(_named(grad_fn, "accum_grad", net),
                         donate_argnums=(2,) if donate else (),
                         out_shardings=(None, None, w_sh))
        return (jitted, out_struct, mutated_names)

    def _build_update_program(self, prep):
        """The window-closing program: ONE fused optimizer update from
        the accumulated grads (overflow detected on the SUM), the
        sentinel digest cond, and freshly ZEROED accumulators returned
        in the donated buffers so the next window starts clean."""
        group_layout, has_ok, donate = (prep.group_layout, prep.has_ok,
                                        prep.donate)
        from .optimizer import fused as _fused

        opt = self._trainer._optimizer
        bodies = [_fused.group_step_fn(opt, mp, has_ok)
                  for mp, _m in group_layout]

        def update_fn(w_list, s_list, acc_list, lrs_g, wds_g, counts_g,
                      rescale, rescale_alt, prev_ok, want_digest):
            _pstore.count_trace("train_step")
            if has_ok:
                with jax.named_scope("loss_scale"):
                    rescale_eff = jnp.where(prev_ok, rescale, rescale_alt)
            else:
                rescale_eff = rescale
            grads = list(acc_list)
            ok = _all_finite(grads, has_ok)
            new_w, new_s = _fused_update(
                bodies, group_layout, w_list, grads, s_list,
                lrs_g, wds_g, counts_g, rescale_eff, ok)
            dig = _digest(want_digest, new_w, new_s, grads)
            with jax.named_scope("grad_accumulate"):
                new_acc = [jnp.zeros_like(a) for a in acc_list]
            return new_w, tuple(new_s), new_acc, ok, dig

        w_sh, s_sh = self._pinned(prep)
        return jax.jit(_named(update_fn, "accum_update", self._net),
                       donate_argnums=(0, 1, 2) if donate else (),
                       out_shardings=(w_sh, s_sh, w_sh, None, None))

    def _grad_lower_args(self, prep, in_specs):
        """Abstract lowering args for the microbatch grad program
        (precompile): mirrors :meth:`_lower_args` minus the optimizer
        tail, plus the accumulator buffers."""
        f32 = jax.ShapeDtypeStruct((), jnp.float32)
        mesh = prep.mesh
        if mesh is not None:
            from .parallel import spmd as _spmd

            n_dp = int(mesh.shape.get(_spmd.DATA_AXIS, 1))
            bsh = _spmd.batch_sharding(mesh)

            def _in_spec(s):
                sh = bsh if (s.shape and s.shape[0] % n_dp == 0) \
                    else prep.rep
                return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

            in_specs = [_in_spec(s) for s in in_specs]
            prev_ok = jax.ShapeDtypeStruct((), jnp.bool_,
                                           sharding=prep.rep)
        else:
            prev_ok = jax.ShapeDtypeStruct((), jnp.bool_)
        w_args = [p.data()._data for p in prep.trainable]
        frozen_args = [prep.params[n].data()._data
                       for n in prep.frozen_names]
        return (w_args, frozen_args, list(self._accum_bufs),
                list(in_specs), jax.random.PRNGKey(0), f32, f32, prev_ok)

    def _update_lower_args(self, prep):
        """Abstract lowering args for the window-closing update program
        (precompile): real param/state/accumulator buffers, abstract
        per-window scalars."""
        from .optimizer import fused as _fused

        f32 = jax.ShapeDtypeStruct((), jnp.float32)
        if prep.mesh is not None:
            prev_ok = jax.ShapeDtypeStruct((), jnp.bool_,
                                           sharding=prep.rep)
            want = jax.ShapeDtypeStruct((), jnp.bool_, sharding=prep.rep)
        else:
            prev_ok = jax.ShapeDtypeStruct((), jnp.bool_)
            want = jax.ShapeDtypeStruct((), jnp.bool_)
        g32 = [jax.ShapeDtypeStruct((len(m),), jnp.float32)
               for _mp, m in prep.group_layout]
        w_args = [p.data()._data for p in prep.trainable]
        s_args = tuple(_fused._unwrap(s) for s in prep.states)
        return (w_args, s_args, list(self._accum_bufs),
                list(g32), list(g32), list(g32), f32, f32, prev_ok, want)

    def _build_program(self, prep, in_struct, ctx, flavor):
        from .gluon import block as _gb
        from .optimizer import fused as _fused

        from .parallel import moe as _moe

        params, names = prep.params, prep.names
        slot_of_name, frozen_names = prep.slot_of_name, prep.frozen_names
        group_layout, has_ok, donate = (prep.group_layout, prep.has_ok,
                                        prep.donate)
        net, loss_fn = self._net, self._loss_fn
        opt = self._trainer._optimizer
        raw_fwd, out_struct, mutated_names = _gb._stage_fn(
            lambda *call_args: loss_fn(net, *call_args),
            params, names, in_struct, True, ctx, flavor)
        bodies = [_fused.group_step_fn(opt, mp, has_ok)
                  for mp, _m in group_layout]
        frozen_pos = {n: j for j, n in enumerate(frozen_names)}
        slot_names, gtrans = self._grad_hook(slot_of_name)

        def step_fn(w_list, s_list, frozen_list, in_list, rng_key,
                    lrs_g, wds_g, counts_g, rescale, scale,
                    scale_alt, rescale_alt, prev_ok, want_digest):
            _pstore.count_trace("train_step")
            # deferred AMP gate: the previous step's flag selects which
            # speculative scale candidate this step really runs with —
            # prev_ok=True (the synchronous gate, or a clean previous
            # step) selects the primary pair bit-exactly via where()
            if has_ok:
                with jax.named_scope("loss_scale"):
                    scale_eff = jnp.where(prev_ok, scale, scale_alt)
                    rescale_eff = jnp.where(prev_ok, rescale, rescale_alt)
            else:
                scale_eff, rescale_eff = scale, rescale

            def fwd(w_l):
                full = [w_l[slot_of_name[n]] if n in slot_of_name
                        else frozen_list[frozen_pos[n]] for n in names]
                with _moe.aux_scope() as auxes:
                    outs, muts = raw_fwd(full, in_list, rng_key)
                # the loss-scale multiply sits INSIDE the differentiated
                # region so grads come out scaled, exactly like backward
                # on amp.scale_loss's scaled loss
                with jax.named_scope("loss_scale"):
                    heads = [o * scale_eff for o in outs] if has_ok \
                        else list(outs)
                    heads = self._fold_aux(auxes, heads, scale_eff,
                                           has_ok)
                return heads, (outs, muts)

            heads, vjp_fn, (outs, muts) = jax.vjp(
                fwd, list(w_list), has_aux=True)
            cts = [jnp.ones(h.shape, h.dtype) for h in heads]
            (grads,) = vjp_fn(cts)
            with jax.named_scope("grad_finite_check"):
                grads = [g.astype(w.dtype) if g.dtype != w.dtype else g
                         for g, w in zip(grads, w_list)]
            grads = self._apply_grad_transform(slot_names, gtrans, grads)
            # kvstore 'device'-path reduce: identity for the supported
            # single-replica/single-worker topology (fused into the
            # program by construction; other topologies fell back)
            ok = _all_finite(grads, has_ok)
            new_w, new_s = _fused_update(
                bodies, group_layout, w_list, grads, s_list,
                lrs_g, wds_g, counts_g, rescale_eff, ok)
            # training-integrity sentinel: on sentinel-cadence steps the
            # program ALSO emits a state fingerprint of the post-update
            # params + optimizer state + grad norm.  lax.cond keeps the
            # fold off non-sentinel steps at runtime; the flag is a
            # traced arg, so cadence never retraces.  Under the SPMD
            # mesh the fold of replicated values is computed redundantly
            # per device — the per-shard values ARE the per-replica
            # digests the corruption vote compares.
            dig = _digest(want_digest, new_w, new_s, grads)
            return (outs, self._pin_mutations(prep, mutated_names, muts),
                    new_w, tuple(new_s), ok, dig)

        w_sh, s_sh = self._pinned(prep)
        jitted = jax.jit(_named(step_fn, "train_step", net),
                         donate_argnums=(0, 1) if donate else (),
                         out_shardings=(None, None, w_sh, s_sh, None, None))
        return (jitted, out_struct, mutated_names)
