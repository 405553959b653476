"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

No MoE exists in the reference (SURVEY.md §5); this is forward-looking
capability required for the TPU build's first-class distributed story.
Design follows the standard TPU recipe: top-k gating with capacity,
einsum-based dense dispatch/combine (MXU-friendly, no dynamic shapes), expert
weights sharded over ``ep`` so the dispatch einsum lowers to an all-to-all
over ICI.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["top_k_gating", "moe_layer", "aux_scope", "record_aux",
           "MoEBlock", "held_experts_layer", "held_buffer_rows",
           "HELD_STATS"]


# ---------------------------------------------------------------------------
# load-balance aux-loss plumbing (the Trainer loss path)
# ---------------------------------------------------------------------------
# A gluon forward has no side channel for the gating aux loss; this
# thread-local scope is it.  cached_step.TrainStep opens the scope around
# the traced forward (compiled AND eager paths) and folds
# MXNET_MOE_AUX_WEIGHT * sum(recorded) into the differentiated loss
# heads, so the load-balance loss reaches the optimizer without touching
# the user's loss_fn signature.

_AUX = threading.local()


@contextlib.contextmanager
def aux_scope():
    """Collect aux losses recorded by MoE blocks during the enclosed
    forward.  Yields the (mutable) list; nesting restores the outer
    scope on exit."""
    prev = getattr(_AUX, "lst", None)
    _AUX.lst = []
    try:
        yield _AUX.lst
    finally:
        _AUX.lst = prev


def record_aux(aux) -> bool:
    """Record one load-balance aux-loss value into the active scope (a
    no-op returning False when no scope is open — e.g. pure-jax callers
    like models/transformer_lm.py that fold the aux themselves)."""
    lst = getattr(_AUX, "lst", None)
    if lst is None:
        return False
    lst.append(aux)
    return True


def top_k_gating(x, gate_w, *, num_experts: int, k: int = 2,
                 capacity_factor: float = 1.25,
                 capacity: Optional[int] = None):
    """Compute dispatch/combine tensors for top-k routing.

    x: [G, S, M] (groups=batch shards, tokens, model dim)
    gate_w: [M, E]
    Returns (dispatch [G, S, E, C] bool-ish float, combine [G, S, E, C],
    aux_loss scalar).  Static shapes throughout: tokens over capacity C are
    dropped (their combine weights are zero), the standard TPU trick to keep
    XLA shapes static (vs the reference's dynamic-shape boolean_mask ops).
    """
    G, S, M = x.shape
    E = num_experts
    if capacity is None:
        capacity = max(1, int(capacity_factor * S * k / E))
    C = capacity

    logits = jnp.einsum("gsm,me->gse", x, gate_w)
    probs = jax.nn.softmax(logits, axis=-1)

    # load-balancing auxiliary loss (Shazeer et al.): mean prob * mean assignment
    top1 = jnp.argmax(probs, axis=-1)
    me = jnp.mean(probs, axis=1)                               # [G, E]
    ce = jnp.mean(jax.nn.one_hot(top1, E, dtype=x.dtype), axis=1)
    aux_loss = jnp.mean(jnp.sum(me * ce, axis=-1)) * E

    dispatch = jnp.zeros((G, S, E, C), dtype=x.dtype)
    combine = jnp.zeros((G, S, E, C), dtype=x.dtype)
    # running per-expert position counters, updated as we take each of k choices
    position_in_expert = jnp.zeros((G, E), dtype=jnp.int32)
    p = probs
    for _ in range(k):
        idx = jnp.argmax(p, axis=-1)                            # [G, S]
        gate = jnp.take_along_axis(p, idx[..., None], axis=-1)[..., 0]
        p = p * (1.0 - jax.nn.one_hot(idx, E, dtype=p.dtype))   # mask chosen
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)        # [G, S, E]
        # position of each token within its chosen expert's queue
        pos = position_in_expert[:, None, :] + jnp.cumsum(onehot, axis=1) - onehot
        pos_tok = jnp.sum(pos * onehot, axis=-1)                # [G, S]
        position_in_expert = position_in_expert + jnp.sum(onehot, axis=1)
        keep = (pos_tok < C).astype(x.dtype)                    # capacity drop
        gate = gate * keep
        pos_oh = jax.nn.one_hot(jnp.minimum(pos_tok, C - 1), C, dtype=x.dtype)
        contrib = onehot.astype(x.dtype)[..., None] * pos_oh[:, :, None, :]
        dispatch = dispatch + contrib * keep[..., None, None]
        combine = combine + contrib * gate[..., None, None]
    return dispatch, combine, aux_loss


def moe_layer(x, gate_w, w_in, w_out, *, k: int = 2,
              capacity_factor: float = 1.25, capacity: Optional[int] = None,
              activation=jax.nn.gelu) -> Tuple[jax.Array, jax.Array]:
    """Dense-dispatch MoE FFN.

    x: [G, S, M]; gate_w: [M, E]; w_in: [E, M, H]; w_out: [E, H, M].
    Shard w_in/w_out over 'ep' on dim 0 (ShardingPlan rule `expert.*` /
    name-aware ``spmd.param_spec``) and XLA turns the dispatch einsums
    into all-to-alls over the ep axis; the expert-dim intermediates carry
    mesh-agnostic ``sharding.constraint(P('ep', 'dp'))`` annotations so
    the partitioner keeps per-expert compute on the expert's devices
    (axes absent from the ambient mesh legalize away silently).
    Returns (output [G, S, M], aux_loss).
    """
    from .sharding import PartitionSpec as _P, constraint as _constraint

    E = gate_w.shape[-1]
    dispatch, combine, aux = top_k_gating(
        x, gate_w, num_experts=E, k=k, capacity_factor=capacity_factor,
        capacity=capacity)
    # [G,S,E,C] x [G,S,M] -> expert inputs [E, G, C, M]
    ep_spec = _P("ep", "dp", None, None)
    expert_in = _constraint(
        jnp.einsum("gsec,gsm->egcm", dispatch, x), ep_spec)
    h = _constraint(
        activation(jnp.einsum("egcm,emh->egch", expert_in, w_in)), ep_spec)
    expert_out = _constraint(
        jnp.einsum("egch,ehm->egcm", h, w_out), ep_spec)
    out = jnp.einsum("gsec,egcm->gsm", combine, expert_out)
    return out, aux


# ---------------------------------------------------------------------------
# the second expert path: sorted, without drops, told which experts it holds
# ---------------------------------------------------------------------------
# One expert-parallel rank's part of a sparse-expert layer.  The router
# scores ALL the experts (sigmoid scores, a selection bias, the k largest of
# score + bias, weights score / sum of the chosen scores times a scaling
# factor: the DeepSeek-V3 router that Nemotron-H takes), the assignments are
# sorted by expert (a counting sort: rank inside the expert plus the
# expert's offset), the rows of the HELD experts alone are gathered into a
# buffer of static size, two grouped products run over it (on a TPU the
# Pallas kernel ``pallas_kernels.grouped_matmul``, elsewhere XLA's
# ``lax.ragged_dot``; group sizes stay on the device), and the result is
# scattered back weighted.  An expert is the model's own: ``w_down
# relu(w_up x)^2`` (Nemotron-H's ``relu2``) or the gated ``w_down
# (silu(w_gate x) * (w_up x))`` (``silu``: DeepSeek-V3's and GLM's), whose
# first product gives gate and up side by side.  What the absent experts would add is
# left out: the exchange that brings it belongs to a mesh with an ``ep``
# axis, and this layer adds nothing that stands in for it.  No row routed to
# a held expert is dropped while the buffer holds; rows beyond it are
# COUNTED, never silently lost (``HELD_STATS``).

HELD_STATS = ("rows_routed", "rows_held", "rows_overflow", "load_max",
              "steps", "tiles_used", "tiles")


def held_buffer_rows(tokens: int, k: int, num_experts: int, num_held: int,
                     capacity_factor: float) -> int:
    """Rows of the held experts' buffer: ``capacity_factor`` times the mean
    share ``tokens * k * num_held / num_experts``, up to the next 256, and
    never more than every assignment (up to the next 256)."""
    mean = tokens * k * num_held / num_experts
    return -(-max(1, min(int(capacity_factor * mean), tokens * k)) // 256) \
        * 256


def _grouped_platform() -> str:
    return jax.default_backend()


def _kernel_products(width: int) -> bool:
    """Do the grouped products take the Pallas kernel
    (``pallas_kernels.grouped_matmul``: a TPU, no mesh of more than one
    device, rows of whole 128-lane columns) or XLA's ``ragged_dot``?
    Decided from what the trace can observe, as the attention core is."""
    from .mesh import current_mesh

    mesh = current_mesh()
    return (_grouped_platform() == "tpu" and width % 128 == 0
            and (mesh is None or mesh.size <= 1))


HIDDEN_ACTS = ("relu2", "silu")


def _expert_hidden(h, hidden_act):
    """What an expert's first product becomes before its second.
    ``relu2``: ``relu(h)^2`` in the type that arrives; ``silu``: ``h``
    holds gate and up side by side, ``silu(gate) * up`` in float32."""
    if hidden_act == "relu2":
        return jnp.square(jax.nn.relu(h))
    gate, up = jnp.split(h.astype(jnp.float32), 2, axis=-1)
    return jax.nn.silu(gate) * up


def held_experts_layer(x, router_w, select_bias, w_up, w_down, *, held,
                       k: int, scaling: float = 1.0,
                       capacity_factor: float = 2.0,
                       hidden_act: str = "relu2"):
    """The held experts' part of the routed result.

    ``x`` (..., M); ``router_w`` (E, M) float32; ``select_bias`` (E,);
    ``w_up`` and ``w_down`` (H, F, M), the weights of the H experts
    ``held`` (global ids, ascending) of the E the router scores.
    ``hidden_act`` is the model's own and decides an expert's form:
    ``relu2``, ``w_down relu(w_up x)^2`` with ``w_up`` (H, M, F);
    ``silu``, the gated ``w_down (silu(w_gate x) * (w_up x))`` with
    ``w_up`` (H, M, 2F) holding ``[w_gate | w_up]`` along its last axis.
    The router's product is float32 at the highest precision whatever ``x``
    is (a rounding there flips a choice); the experts' products take ``x``'s
    type.  The buffer holds ``capacity_factor`` times the mean share
    (``held_buffer_rows``).
    Returns ``(out, stats)``: ``out`` of ``x``'s shape and type, ``stats``
    float32 ``HELD_STATS`` of this call (``load_max`` the busiest held
    expert's rows, ``steps`` 1; ``tiles_used`` of the buffer's ``tiles`` are
    the held experts' own, at least one each, and the kernel's products pay
    for those alone; both 0 on the ``ragged_dot`` path, which has no
    tiles)."""
    from ..ops import pallas_kernels as _pk

    if hidden_act not in HIDDEN_ACTS:
        raise ValueError(f"hidden_act={hidden_act!r}: one of {HIDDEN_ACTS}")
    parts = 2 if hidden_act == "silu" else 1      # products side by side
    if w_up.shape[2] != parts * w_down.shape[1]:
        raise ValueError(
            f"hidden_act={hidden_act!r}: w_up {w_up.shape} must be "
            f"{parts} x w_down's hidden width {w_down.shape[1]}")
    shape, dtype = x.shape, x.dtype
    x = x.reshape(-1, shape[-1])
    tokens, num_experts = x.shape[0], router_w.shape[0]
    held = tuple(int(e) for e in held)
    num_held = len(held)
    if sorted(set(held)) != list(held) or not held \
            or held[-1] >= num_experts or w_up.shape[0] != num_held:
        raise ValueError(f"held={held}: ascending ids below {num_experts}, "
                         f"one for each of w_up's {w_up.shape[0]} experts")
    f32 = jnp.float32
    kernel = _kernel_products(x.shape[1])
    tile = _pk.GROUP_TILE
    rows = held_buffer_rows(tokens, k, num_experts, num_held,
                            capacity_factor)
    if kernel:
        # every expert's rows start on a tile and every expert owns one:
        # at most a tile an expert is lost to the rounding
        rows = -(-rows // tile) * tile + num_held * tile

    with jax.named_scope("MoERouter"):
        scores = jax.nn.sigmoid(jnp.einsum(
            "tm,em->te", x.astype(f32), router_w.astype(f32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(scores + select_bias.astype(f32), k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * scaling

    with jax.named_scope("MoEDispatch"):
        # a counting sort by expert: a row's place is its expert's start
        # plus its rank among the expert's rows
        slot_of = jnp.full((num_experts,), -1, jnp.int32).at[
            jnp.asarray(held)].set(jnp.arange(num_held, dtype=jnp.int32))
        slot = slot_of[chosen].reshape(-1)                 # (tokens * k,)
        mine = slot[:, None] == jnp.arange(num_held)[None, :]
        count = jnp.sum(mine, axis=0, dtype=jnp.int32)     # rows an expert
        room = jnp.maximum(-(-count // tile), 1) * tile if kernel else count
        start = jnp.cumsum(room) - room
        rank = jnp.cumsum(mine, axis=0, dtype=jnp.int32) - 1
        at = jnp.sum(jnp.where(mine, rank + start[None, :], 0), axis=1)
        is_held = slot >= 0
        at = jnp.where(is_held, at, rows)                  # dropped below
        token = jnp.arange(tokens * k, dtype=jnp.int32) // k
        source = jnp.zeros((rows,), jnp.int32).at[at].set(token, mode="drop")
        row_w = jnp.zeros((rows,), f32).at[at].set(
            weights.reshape(-1), mode="drop")
        # a row nothing landed on: zeros in, zeros out, whatever a grouped
        # product leaves there, forward and backward
        filled = jnp.zeros((rows,), bool).at[at].set(True, mode="drop")[
            :, None]
        n_held = jnp.sum(count)
        tiles = rows // tile if kernel else 0
        used = jnp.minimum((start[-1] + room[-1]) // tile, tiles)
        stats = jnp.stack([
            jnp.asarray(tokens * k, f32), n_held.astype(f32),
            jnp.sum(is_held & (at >= rows)).astype(f32),
            jnp.max(count).astype(f32), jnp.asarray(1.0, f32),
            used.astype(f32), jnp.asarray(tiles, f32)])
        gathered = jnp.where(filled, x[source], 0)         # (rows, M)

    with jax.named_scope("MoEExperts"):
        if kernel:
            tile_group = jnp.clip(jnp.searchsorted(
                start, jnp.arange(tiles, dtype=jnp.int32) * tile,
                side="right") - 1, 0, num_held - 1).astype(jnp.int32)
            used = used.reshape(1).astype(jnp.int32)
            hidden = w_down.shape[1]
            pad = -hidden % 128               # the hidden width in lanes
            up = w_up.astype(dtype)
            if parts == 1:
                up = jnp.pad(up, ((0, 0), (0, 0), (0, pad)))
            elif pad:     # gate and up lie side by side: each padded alone
                up = jnp.pad(
                    up.reshape(up.shape[:2] + (parts, hidden)),
                    ((0, 0), (0, 0), (0, 0), (0, pad))).reshape(
                        up.shape[:2] + (parts * (hidden + pad),))
            down = jnp.pad(w_down.astype(dtype), ((0, 0), (0, pad), (0, 0)))
            h = _expert_hidden(
                _pk.grouped_matmul(gathered, up, tile_group, used),
                hidden_act)
            y = _pk.grouped_matmul(h.astype(dtype), down, tile_group, used)
        else:
            sizes = jnp.clip(rows - start, 0, count)       # what fits
            h = jax.lax.ragged_dot(gathered, w_up.astype(dtype), sizes)
            h = _expert_hidden(jnp.where(filled, h, 0), hidden_act)
            y = jax.lax.ragged_dot(h.astype(dtype), w_down.astype(dtype),
                                   sizes)

    with jax.named_scope("MoECombine"):
        y = jnp.where(filled, y.astype(f32) * row_w[:, None], 0.0)
        out = jnp.zeros(x.shape, dtype).at[source].add(y.astype(dtype))
    return out.reshape(shape), jax.lax.stop_gradient(stats)


# ---------------------------------------------------------------------------
# Gluon adapter: expert-parallel MoE FFN as a trainable Block
# ---------------------------------------------------------------------------

_MOE_BLOCK_CLS = None


def _moe_block_cls():
    """Build the MoEBlock class lazily: gluon imports here (not at module
    import) keep ``mxnet_tpu.parallel`` free of an import cycle through
    the gluon package."""
    global _MOE_BLOCK_CLS
    if _MOE_BLOCK_CLS is not None:
        return _MOE_BLOCK_CLS

    from .. import autograd as _ag
    from ..context import current_context
    from ..gluon.block import Block, jax_bridge
    from ..gluon.parameter import Parameter
    from ..ndarray import NDArray
    from ..ndarray.ndarray import _wrap

    class _Holder(Block):
        """Bare parameter/child holder so collect_params yields the
        canonical ``expert.*`` structural names the ep sharding rule
        (``spmd.param_spec``) and ShardingPlans match on."""

    class MoEBlock(Block):
        """Dense-dispatch top-k MoE FFN (:func:`moe_layer`) as a gluon
        block in the one donated step program.

        Parameters are named for the ep placement contract —
        ``gate.weight [M, E]`` (replicated), ``expert.ffn_1.weight
        [E, M, H]`` and ``expert.ffn_2.weight [E, H, M]`` (sharded
        ``P('ep')`` on dim 0 by name-aware ``spmd.param_spec`` when the
        mesh has a real ``ep`` axis).  The gating load-balance aux loss
        is recorded into the ambient :func:`aux_scope`; the TrainStep
        folds ``MXNET_MOE_AUX_WEIGHT * sum`` into the differentiated
        loss heads on both the compiled and eager paths, so the balance
        penalty reaches the optimizer without widening the user's
        loss_fn contract.  Input ``x`` is ``[G, S, M]`` (groups, tokens,
        model dim); output matches.
        """

        def __init__(self, units: int, hidden: int, num_experts: int, *,
                     k: int = 2, capacity_factor: float = 1.25,
                     capacity: Optional[int] = None,
                     activation=jax.nn.gelu, dtype: str = "float32"):
            super().__init__()
            self._units = units
            self._hidden = hidden
            self._num_experts = num_experts
            self._k = k
            self._capacity_factor = capacity_factor
            self._capacity = capacity
            self._activation = activation
            self.gate = _Holder()
            self.gate.weight = Parameter(
                "weight", shape=(units, num_experts), dtype=dtype)
            self.expert = _Holder()
            self.expert.ffn_1 = _Holder()
            self.expert.ffn_1.weight = Parameter(
                "weight", shape=(num_experts, units, hidden), dtype=dtype)
            self.expert.ffn_2 = _Holder()
            self.expert.ffn_2.weight = Parameter(
                "weight", shape=(num_experts, hidden, units), dtype=dtype)

        def _moe_fn(self):
            kw = dict(k=self._k, capacity_factor=self._capacity_factor,
                      capacity=self._capacity,
                      activation=self._activation)

            def fn(x, gw, wi, wo):
                return moe_layer(x, gw, wi, wo, **kw)

            return fn

        def forward(self, x):
            gw = self.gate.weight.data()
            wi = self.expert.ffn_1.weight.data()
            wo = self.expert.ffn_2.weight.data()
            if _ag.is_recording() and not isinstance(
                    gw._data, jax.core.Tracer):
                out, aux = jax_bridge(self._moe_fn(), x, gw, wi, wo)
                record_aux(aux)
                return out
            ctx = x.ctx if isinstance(x, NDArray) else current_context()
            raw = x._data if isinstance(x, NDArray) else jnp.asarray(x)
            out, aux = self._moe_fn()(raw, gw._data, wi._data, wo._data)
            record_aux(aux)
            return _wrap(out, ctx)

    _MOE_BLOCK_CLS = MoEBlock
    return _MOE_BLOCK_CLS


def __getattr__(name):
    if name == "MoEBlock":
        return _moe_block_cls()
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
