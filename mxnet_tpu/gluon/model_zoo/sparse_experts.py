"""Sparse experts with a shared expert, as one expert-parallel rank holds
them: the block behind ``NemotronHMoE`` and ``Glm4MoeLiteMoE``, and what
every such layer counts on the device.

``num_experts`` routed experts scored by a sigmoid router with a constant
selection bias, ``top_k`` a token, weights normalised over the chosen and
scaled (DeepSeek-V3's router); plus a shared expert that every token goes
through, which the model brings.  ``held`` names the routed experts whose
weights live here (default: all); the rest of the routed result is another
rank's (``parallel.moe.held_experts_layer``).  The routed experts' form is
the model's own ``hidden_act``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ... import autograd, initializer, telemetry
from ...ndarray.ndarray import invoke
from ...parallel import moe as _moe
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["SparseExperts"]


# -- what the expert layers counted, on the device ---------------------------
#
# Every expert layer accumulates ``moe.HELD_STATS`` in a parameter of its own
# (``grad_req='null'``, written by the step program like a batch norm's
# running statistics): no host read is added to a step.  The gauges below
# read the layers' counts when somebody takes a ``telemetry.snapshot()``;
# ``telemetry.events()`` polls them too, and rows that did not fit a buffer
# become a ``fallback`` event, which fails a benchmark cell's ``correct``.

_LAYERS = []      # every expert layer this process built, as _Counted
(_ROUTED, _HELD, _OVERFLOW, _LOAD_MAX, _STEPS, _TILES_USED,
 _TILES) = range(len(_moe.HELD_STATS))


class _Counted:
    """What outlives an expert layer: its counts parameter (a few numbers
    on the device), what share of the experts it holds, and how much
    overflow was already reported.  A benchmark reads the gauges after the
    net is gone."""

    def __init__(self, layer):
        self.counts = layer.counts
        self.held_share = len(layer._held) / layer._num_experts
        self.num_experts = layer._num_experts
        self.overflow_reported = 0.0


def _counts():
    """``[(record, its counts)]`` of every expert layer whose counts
    are concrete (one host read each)."""
    out = []
    for rec in list(_LAYERS):
        data = rec.counts._data
        raw = data[0]._data if data else None
        if raw is None or isinstance(raw, jax.core.Tracer):
            continue
        try:
            out.append((rec, [float(v) for v in jax.device_get(raw)]))
        except RuntimeError:          # donated to a step still in flight
            continue
    return out


def _total(index):
    return lambda: sum(c[index] for _, c in _counts())


def _rows_held_share():
    """Rows the held experts got over the mean share of as many experts."""
    counts = _counts()
    mean = sum(c[_ROUTED] * rec.held_share for rec, c in counts)
    return sum(c[_HELD] for _, c in counts) / mean if mean else None


def _load_max_over_mean():
    """The busiest held expert's rows in any step over an expert's mean."""
    ratios = [c[_LOAD_MAX] / (c[_ROUTED] / c[_STEPS] / rec.num_experts)
              for rec, c in _counts() if c[_STEPS]]
    return max(ratios) if ratios else None


def _tiles_used_share():
    """Tiles of the buffers that are a held expert's own (its rows' tiles,
    at least one each) over the buffers' tiles: what the grouped kernels'
    time follows over what the dispatch's does (no tiles: the products were
    XLA's ``ragged_dot``)."""
    counts = _counts()
    tiles = sum(c[_TILES] for _, c in counts)
    return sum(c[_TILES_USED] for _, c in counts) / tiles if tiles else None


def _poll_overflow():
    for rec, c in _counts():
        new = c[_OVERFLOW] - rec.overflow_reported
        if new > 0:
            rec.overflow_reported = c[_OVERFLOW]
            telemetry.event("fallback", "moe.rows_overflow", rows=new,
                            why="rows routed to held experts beyond the "
                                "buffer's static size were left out")


telemetry.gauge_fn("moe.rows_routed", _total(_ROUTED),
                   "assignments (tokens x experts a token) the live expert "
                   "layers routed in training steps")
telemetry.gauge_fn("moe.rows_held", _total(_HELD),
                   "of those, rows routed to an expert held here")
telemetry.gauge_fn("moe.rows_overflow", _total(_OVERFLOW),
                   "rows routed to a held expert that did not fit the "
                   "buffer (left out of the result; also a fallback event)")
telemetry.gauge_fn("moe.load_max", lambda: max(
    (c[_LOAD_MAX] for _, c in _counts()), default=0.0),
    "rows of the busiest held expert in any one training step")
telemetry.gauge_fn("moe.steps", _total(_STEPS),
                   "training-mode calls of the live expert layers")
telemetry.gauge_fn("moe.rows_held_share", _rows_held_share,
                   "rows the held experts got over their mean share")
telemetry.gauge_fn("moe.load_max_over_mean", _load_max_over_mean,
                   "the busiest held expert's rows over an expert's mean")
telemetry.gauge_fn("moe.tiles_used_share", _tiles_used_share,
                   "tiles of the buffers that are a held expert's own (the "
                   "grouped kernels pay for these) over the buffers' tiles")
telemetry.event_source(_poll_overflow)


class SparseExperts(HybridBlock):
    """Router, held routed experts, the model's ``shared_expert`` block and
    the layer's device counts.  ``hidden_act`` is ``relu2`` (an expert is
    ``down relu(up x)^2``, ``experts_up`` (held, hidden, width)) or ``silu``
    (the gated ``down (silu(gate x) * (up x))``, ``experts_up`` (held,
    hidden, 2 x width) holding ``[gate | up]``).  The held experts' buffer
    is ``capacity_factor`` times their mean share of the rows; rows beyond
    it are counted and become a ``fallback`` event.  A subclass's name is
    the layer's scope in the device trace."""

    def __init__(self, hidden_size, num_experts, top_k, moe_intermediate_size,
                 shared_expert, routed_scaling_factor=1.0,
                 held: Optional[Sequence[int]] = None, hidden_act="relu2",
                 init=None, down_init=None, capacity_factor=2.0):
        super().__init__()
        self._held = tuple(range(num_experts) if held is None else held)
        self._num_experts, self._top_k = num_experts, top_k
        self._scaling, self._hidden_act = routed_scaling_factor, hidden_act
        self._capacity = capacity_factor
        n = len(self._held)
        parts = 2 if hidden_act == "silu" else 1
        init = init or initializer.Normal(0.02)
        self.router_weight = Parameter(
            "router_weight", shape=(num_experts, hidden_size), init=init)
        # constant: the sources give no update rate.  Small and nonzero, so
        # that leaving it out changes choices.
        self.e_score_correction_bias = Parameter(
            "e_score_correction_bias", shape=(num_experts,),
            init=initializer.Uniform(0.05), grad_req="null",
            differentiable=False)
        self.experts_up = Parameter(
            "experts_up",
            shape=(n, hidden_size, parts * moe_intermediate_size), init=init)
        self.experts_down = Parameter(
            "experts_down", shape=(n, moe_intermediate_size, hidden_size),
            init=down_init or init)
        self.counts = Parameter(
            "counts", shape=(len(_moe.HELD_STATS),), init=initializer.Zero(),
            grad_req="null", differentiable=False)
        self.shared_expert = shared_expert
        _LAYERS.append(_Counted(self))

    def routed(self, x):
        """The held experts' part alone (no shared expert)."""
        ctx = x.ctx
        out, stats = invoke("held_experts", [
            x, self.router_weight.data(ctx),
            self.e_score_correction_bias.data(ctx),
            self.experts_up.data(ctx), self.experts_down.data(ctx)],
            {"held": self._held, "k": self._top_k, "scaling": self._scaling,
             "hidden_act": self._hidden_act,
             "capacity_factor": self._capacity})
        if autograd.is_training():
            counts = self.counts.data(ctx)
            with autograd.pause():
                old, new = counts._data, stats._data
                counts._set_data(jnp.where(
                    jnp.arange(old.shape[0]) == _LOAD_MAX,
                    jnp.maximum(old, new), old + new))
        return out

    def forward(self, x):
        return self.routed(x) + self.shared_expert(x)
