"""Fused multi-tensor optimizer step.

The eager ``Trainer``/``KVStore`` path used to dispatch one XLA program per
parameter per step (``Trainer._update`` -> ``updater(idx, grad, weight)``
per weight, mirroring the reference's per-key engine pushes) — a ResNet-50
step paid ~160 host round-trips before any math ran.  This module collapses
that to ONE ``jax.jit``-compiled, buffer-donated program per *parameter
group*: trainable parameters are grouped by (dtype, optimizer hyper-param
signature, multi-precision flag) and the whole group's (weights, grads,
states) pytree updates in a single dispatch — the eager analog of the
reference's multi-tensor ops (``src/operator/contrib/multi_lamb.cc``,
``multi_sgd``) and of ``ShardedTrainer``'s whole-step compiled program.

Requirements on the optimizer: a functional
``Optimizer.fused_update(weights, grads, states, lrs, wds, counts)`` rule
(SGD/Adam/AdaGrad/LAMB implement it; others fall back transparently to the
scalar per-parameter loop).  Per-step values that must not force a re-trace
— learning rates, weight decays, update counts, rescale_grad, the AMP
all-finite flag — enter the program as traced arguments; everything else
(hyper-params, shapes, dtypes, state structure) keys the compiled-program
cache, so a group re-traces only when the parameter set itself changes.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import bounded_cache_put
from ..ndarray import NDArray

__all__ = ["supports", "enabled", "grouped_update", "all_finite",
           "group_step_fn", "trace_count", "dispatch_count",
           "reset_counters"]

# compiled group programs, keyed on (optimizer signature, group dtype, mp,
# shapes/dtypes of weights+grads, state tree structure, ok-flag presence)
_GROUP_JIT: "OrderedDict" = OrderedDict()
_GROUP_CAP = 64
_FINITE_JIT: Dict[Any, Any] = {}

# observability: fused.trace bumps when a group/finite-check program body
# is (re)traced; fused.dispatch bumps per compiled-program launch.  Tests
# assert re-trace stays at 0 across repeated step() calls.
from .. import telemetry as _telemetry  # noqa: E402

_TRACE = _telemetry.counter(
    "fused.trace", "fused-optimizer group/finite-check program bodies "
    "(re)traced")
_DISPATCH = _telemetry.counter(
    "fused.dispatch", "fused-optimizer compiled-program launches")


def trace_count() -> int:
    return int(_TRACE.value)


def dispatch_count() -> int:
    return int(_DISPATCH.value)


def reset_counters() -> None:
    _TRACE.reset()
    _DISPATCH.reset()


def supports(opt) -> bool:
    """True when the optimizer carries a functional multi-tensor rule."""
    from .optimizer import Optimizer

    return (opt is not None and getattr(opt, "use_fused_step", False)
            and type(opt).fused_update is not Optimizer.fused_update)


def enabled(opt) -> bool:
    """Fused path active for this optimizer (rule present + knob on)."""
    from .. import config as _config

    if not _config.get("MXNET_FUSED_OPTIMIZER"):
        return False
    return supports(opt)


# -- state pytree helpers ---------------------------------------------------


def _unwrap(s):
    if s is None:
        return None
    if isinstance(s, NDArray):
        return s._data
    return tuple(_unwrap(x) for x in s)


def _write(dst, new) -> None:
    if dst is None:
        return
    if isinstance(dst, NDArray):
        dst._set_data(new)
        return
    for d, n in zip(dst, new):
        _write(d, n)


def _struct(s):
    if s is None:
        return None
    if isinstance(s, NDArray):
        return (tuple(s.shape), s._data.dtype)
    return tuple(_struct(x) for x in s)


def _tree_where(ok, new, old):
    if old is None:
        return None
    if isinstance(old, (tuple, list)):
        return tuple(_tree_where(ok, n, o) for n, o in zip(new, old))
    return jnp.where(ok, new, old)


def _is_mp_state(opt, weight, state) -> bool:
    """Per-parameter multi-precision detection: fp16 weight whose state is
    the (fp32 master, inner state) pair built by
    ``create_state_multi_precision``."""
    return (bool(opt.multi_precision)
            and weight.dtype == onp.float16
            and isinstance(state, (tuple, list)) and len(state) == 2
            and isinstance(state[0], NDArray)
            and state[0].dtype == onp.float32
            and tuple(state[0].shape) == tuple(weight.shape))


# -- the all-finite check (AMP overflow, folded into the step) --------------


def all_finite(arrays: Sequence) -> jnp.ndarray:
    """Reduce finiteness over every array in ONE compiled program; returns
    a device bool scalar — no host sync.  ``Trainer.step`` threads this
    flag into each group program (the update is skipped on-device when it
    is False), and ``LossScaler.has_overflow`` reads it once on host."""
    arrs = [a._data if isinstance(a, NDArray) else a for a in arrays
            if a is not None]
    if not arrs:
        return jnp.asarray(True)
    key = tuple((tuple(a.shape), a.dtype) for a in arrs)
    fn = _FINITE_JIT.get(key)
    if fn is None:

        def check(xs):
            _TRACE.inc()
            return jnp.all(jnp.stack([jnp.isfinite(x).all() for x in xs]))

        fn = bounded_cache_put(_FINITE_JIT, key, jax.jit(check))
    _DISPATCH.inc()
    return fn(arrs)


# -- grouped update ---------------------------------------------------------


def grouped_update(opt, indices, weights, grads, states) -> bool:
    """Apply the optimizer to every parameter as one compiled program per
    (dtype, multi-precision) group.  Returns True when handled; False
    means the caller must run the scalar per-parameter loop.  Reads the
    optional AMP flag from ``opt._fused_skip_ok`` (a device bool scalar
    installed by ``Trainer.step``): when present, each group program
    applies ``where(ok, new, old)`` so an overflowed step is skipped
    without a host sync."""
    if not enabled(opt):
        return False
    n = len(indices)
    if n == 0:
        return True
    for w, g in zip(weights, grads):
        if not isinstance(w, NDArray) or not isinstance(g, NDArray) \
                or tuple(w.shape) != tuple(g.shape):
            return False
    lrs = opt._get_lrs(list(indices))
    wds = opt._get_wds(list(indices))
    counts = [opt._index_update_count.get(i, opt.num_update)
              for i in indices]
    ok = getattr(opt, "_fused_skip_ok", None)

    groups: "OrderedDict" = OrderedDict()
    for i in range(n):
        mp = _is_mp_state(opt, weights[i], states[i])
        groups.setdefault((weights[i]._data.dtype, mp), []).append(i)
    for (_dt, mp), members in groups.items():
        _apply_group(opt, mp,
                     [weights[i] for i in members],
                     [grads[i] for i in members],
                     [states[i] for i in members],
                     [lrs[i] for i in members],
                     [wds[i] for i in members],
                     [counts[i] for i in members],
                     ok)
    return True


def group_step_fn(opt, mp: bool, has_ok: bool):
    """Traceable multi-tensor group-update body: pure jnp over the group's
    (weights, grads, states) with lrs/wds/counts/rescale/ok as traced
    values.  Shared by the eager fused path (``_build`` jits it per group)
    and by ``cached_step.TrainStep``, which inlines it into the whole
    train-step program — one numerics definition, two compilation
    granularities."""
    def group_step(w_data, g_data, s_data, lrs, wds, counts, rescale, ok):
        n = len(w_data)
        lr_l = [lrs[i] for i in range(n)]
        wd_l = [wds[i] for i in range(n)]
        t_l = [counts[i] for i in range(n)]
        # rescale_grad rides in as a traced scalar so a changed batch size
        # does not force a re-trace; swap it in only for the trace
        saved = opt.rescale_grad
        opt.rescale_grad = rescale
        try:
            if mp:
                masters = [s[0] for s in s_data]
                inner = [s[1] for s in s_data]
                g32 = [g.astype(jnp.float32) for g in g_data]
                new_m, new_inner = opt.fused_update(
                    masters, g32, inner, lr_l, wd_l, t_l)
                new_w = [m.astype(w.dtype) for m, w in zip(new_m, w_data)]
                new_s = tuple((m, i2) for m, i2 in zip(new_m, new_inner))
            else:
                new_w, new_s = opt.fused_update(
                    list(w_data), list(g_data), list(s_data),
                    lr_l, wd_l, t_l)
                new_w = [nw.astype(w.dtype)
                         for nw, w in zip(new_w, w_data)]
                new_s = tuple(new_s)
        finally:
            opt.rescale_grad = saved
        if has_ok:
            new_w = [jnp.where(ok, nw, w)
                     for nw, w in zip(new_w, w_data)]
            new_s = tuple(_tree_where(ok, ns, s)
                          for ns, s in zip(new_s, s_data))
        return list(new_w), new_s

    return group_step


def _build(opt, mp: bool, has_ok: bool, donate: bool):
    body = group_step_fn(opt, mp, has_ok)

    def group_step(*args):
        _TRACE.inc()
        return body(*args)

    # donation aliases the old weight/state HBM into the outputs (the
    # whole point of the fused step on chip); CPU has no donation support
    # and would only warn
    return jax.jit(group_step, donate_argnums=(0, 2) if donate else ())


def _apply_group(opt, mp, ws, gs, ss, lrs, wds, counts, ok) -> None:
    has_ok = ok is not None
    donate = jax.default_backend() not in ("cpu",)
    sig = (type(opt).__name__, opt._fused_signature(), mp, has_ok, donate,
           tuple((tuple(w.shape), w._data.dtype) for w in ws),
           tuple((tuple(g.shape), g._data.dtype) for g in gs),
           tuple(_struct(s) for s in ss))
    fn = _GROUP_JIT.get(sig)
    if fn is None:
        fn = bounded_cache_put(_GROUP_JIT, sig,
                               _build(opt, mp, has_ok, donate),
                               cap=_GROUP_CAP)
    else:
        _GROUP_JIT.move_to_end(sig)
    new_w, new_s = fn(
        [w._data for w in ws],
        [g._data for g in gs],
        tuple(_unwrap(s) for s in ss),
        jnp.asarray(lrs, jnp.float32),
        jnp.asarray(wds, jnp.float32),
        jnp.asarray(counts, jnp.float32),
        jnp.asarray(float(opt.rescale_grad), jnp.float32),
        ok if has_ok else jnp.asarray(True))
    _DISPATCH.inc()
    for w, nw in zip(ws, new_w):
        w._set_data(nw)
    for s, ns in zip(ss, new_s):
        _write(s, ns)
