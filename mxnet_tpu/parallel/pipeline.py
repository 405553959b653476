"""Pipeline parallelism over the ``pp`` mesh axis.

Absent from the reference (only manual device placement existed; SURVEY.md
§2.3).  TPU-native design: the schedule is a GPipe loop written as
``lax.scan`` inside ``shard_map`` — activations move between neighbour
devices with ``ppermute`` (one ICI hop), microbatches fill/drain the bubble.

This is the "collective pipelining" pattern: because every device runs the
same scanned program on its own stage's weights, the whole pipeline is one
SPMD computation XLA can overlap (permute of microbatch i+1 rides under
compute of microbatch i).

Two APIs:

- :func:`pipelined` — fast path for *identical* stages (stage params stacked
  on a leading axis sharded over ``pp``, shape-preserving stage fn).
- :class:`HeteroPipeline` — *heterogeneous* stages (e.g. embed → block stack
  → head) with per-stage functions, per-stage parameter pytrees, and
  non-shape-preserving boundaries.  Each stage's params are flattened into
  one padded fp32 buffer; the buffers are stacked into ``[n_stages, P]``
  sharded over ``pp`` so device *i* holds only stage *i*'s weights.  Stage
  dispatch is a ``lax.switch`` on the device's pp index; activations cross
  stage boundaries in a packed "wire" buffer sized to the largest boundary
  (specs derived once via ``jax.eval_shape``).  Microbatch gradient
  accumulation is inherent: differentiating through the scan sums each
  stage's weight gradient over all its microbatches (GPipe schedule); with
  ``remat=True`` each per-step stage call is rematerialised in the backward
  pass, bounding live activation memory to the 1F1B profile (wire buffers
  only) instead of full GPipe stashes.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

__all__ = ["pipeline_apply", "pipelined", "stack_stage_params",
           "HeteroPipeline", "PipelineBlock", "bubble_fraction"]

# largest integer magnitude fp32 represents exactly: the packed wire
# casts every leaf to fp32, so wider values would silently round
_WIRE_EXACT_MAX = 2 ** 24


def bubble_fraction(n_stages: int, num_microbatches: int) -> float:
    """GPipe bubble fraction: the fill/drain steps (``n_stages - 1``) as a
    share of the whole schedule (``num_microbatches + n_stages - 1``)."""
    return (n_stages - 1) / float(num_microbatches + n_stages - 1)


def _wire_wide_int(dtype) -> bool:
    dt = jnp.dtype(dtype)
    return dt.kind in "iu" and dt.itemsize >= 4


def _check_wire_tree(tree, where: str, *, allow_abstract_32: bool = False):
    """Refuse leaves the packed fp32 wire cannot carry exactly.

    Narrow integers (bool/int8/int16/uint8/uint16) always round-trip.
    Wide integers (>= 32-bit) round-trip only below 2**24: concrete
    leaves are value-checked; abstract leaves (``jax.eval_shape``-derived
    stage boundaries, ShapeDtypeStruct examples) cannot be bounds-checked
    at wire-spec derivation time, so they refuse — except 32-bit example
    INPUTS when ``allow_abstract_32`` (the documented token-id path,
    vocab ids << 2**24).  Raising here, at ``HeteroPipeline.__init__``,
    replaces the old silent precision loss in ``_tree_pack`` /
    ``_batched_pack``.
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        if not _wire_wide_int(getattr(leaf, "dtype", jnp.float32)):
            continue
        name = jax.tree_util.keystr(path) or "<root>"
        dt = jnp.dtype(leaf.dtype)
        from ..base import MXNetError

        concrete = not isinstance(leaf, jax.ShapeDtypeStruct) and \
            hasattr(leaf, "__array__")
        if concrete:
            # graftlint: disable=host-sync -- one-time __init__ validation
            # of concrete example/param values, never inside the step
            arr = onp.asarray(leaf)
            vmax = max(abs(int(arr.min())), abs(int(arr.max()))) \
                if arr.size else 0
            if vmax >= _WIRE_EXACT_MAX:
                raise MXNetError(
                    f"HeteroPipeline wire precision: {where} leaf "
                    f"{name} (dtype {dt.name}) holds |value| {vmax} >= "
                    "2**24, which the packed fp32 wire cannot represent "
                    "exactly. Keep integer leaves below 2**24 or cast "
                    "to float32 (or a <=16-bit integer) before the "
                    "pipeline boundary.")
            continue
        if dt.itemsize == 4 and allow_abstract_32:
            continue
        raise MXNetError(
            f"HeteroPipeline wire precision: {where} leaf {name} has "
            f"abstract dtype {dt.name}; integer values >= 2**24 do not "
            "round-trip through the packed fp32 wire and a "
            f"{'64-bit' if dt.itemsize >= 8 else 'computed'} integer "
            "boundary cannot be bounds-checked at wire-spec derivation "
            "time. Cast to float32 (or a <=16-bit integer) at the "
            "stage boundary.")


def stack_stage_params(per_stage_params):
    """[{name: arr}, ...] (one dict per stage, same structure) ->
    {name: arr stacked on new leading stage axis} — shard dim 0 over 'pp'."""
    keys = per_stage_params[0].keys()
    return {k: jnp.stack([p[k] for p in per_stage_params]) for k in keys}


def pipeline_apply(stage_fn: Callable, stacked_params, x, *,
                   num_microbatches: int, axis_name: str = "pp"):
    """Run ``stage_fn(params, act) -> act`` through all pipeline stages.

    Call INSIDE shard_map: ``stacked_params`` leaves have a leading stage dim
    already sharded to size 1 locally (this device's stage); ``x`` is the
    full batch input [B, ...] present on stage 0 (replicated arrival is fine
    — non-first stages ignore their input).  Returns the final stage's
    output, valid on the LAST stage (others hold garbage; caller selects).
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    local_params = jax.tree_util.tree_map(lambda a: a[0], stacked_params)

    B = x.shape[0]
    assert B % num_microbatches == 0, "batch must divide microbatches"
    mb = B // num_microbatches
    micro = x.reshape((num_microbatches, mb) + x.shape[1:])

    total_steps = num_microbatches + n - 1
    buf = jnp.zeros((mb,) + x.shape[1:], dtype=x.dtype)      # inbound act
    outs = jnp.zeros((num_microbatches, mb) + x.shape[1:], dtype=x.dtype)

    def step(carry, t):
        buf, outs = carry
        # stage 0 injects microbatch t (while t < num_microbatches)
        feed = micro[jnp.minimum(t, num_microbatches - 1)]
        cur = jnp.where(idx == 0, feed, buf)
        act = stage_fn(local_params, cur)
        # last stage records its result for microbatch t - (n-1)
        out_slot = t - (n - 1)
        outs = jnp.where(
            (idx == n - 1) & (out_slot >= 0),
            lax.dynamic_update_index_in_dim(
                outs, act, jnp.clip(out_slot, 0, num_microbatches - 1), 0),
            outs)
        # shift activations forward one stage
        perm = [(i, (i + 1) % n) for i in range(n)]
        buf = lax.ppermute(act, axis_name, perm=perm)
        return (buf, outs), None

    (_, outs), _ = lax.scan(step, (buf, outs), jnp.arange(total_steps))
    out = outs.reshape((B,) + x.shape[1:])
    # deliver final output from last stage to all (so loss is replicated)
    src = n - 1
    mask = (idx == src).astype(out.dtype)
    return lax.psum(out * mask, axis_name)


def pipelined(stage_fn: Callable, mesh: Mesh, *, num_microbatches: int,
              axis_name: str = "pp", param_spec=None, x_spec=None):
    """shard_map wrapper: stacked params sharded over pp on dim 0, input
    replicated over pp, output replicated."""
    if param_spec is None:
        param_spec = P(axis_name)
    if x_spec is None:
        x_spec = P()
    fn = partial(pipeline_apply, stage_fn, num_microbatches=num_microbatches,
                 axis_name=axis_name)
    return shard_map(fn, mesh=mesh, in_specs=(param_spec, x_spec),
                     out_specs=P(), check_vma=False)


# ---------------------------------------------------------------------------
# Heterogeneous pipeline
# ---------------------------------------------------------------------------

def _tree_pack_spec(tree):
    """(treedef, [(shape, dtype, offset, size)], total_size) for packing a
    pytree into one flat fp32 vector."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    specs, off = [], 0
    for leaf in leaves:
        n = int(onp.prod(leaf.shape)) if leaf.shape else 1
        specs.append((tuple(leaf.shape), jnp.dtype(leaf.dtype), off, n))
        off += n
    return treedef, specs, off


def _tree_pack(tree, size: int):
    """Flatten + concat a pytree into an fp32 vector padded to ``size``.

    Integer leaves are value-cast (exact below 2**24 — tokens/labels); all
    float leaves round-trip exactly through fp32 except fp64 (unused here).
    """
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((size,), jnp.float32)
    flat = jnp.concatenate(
        [jnp.ravel(l).astype(jnp.float32) for l in leaves])
    return jnp.pad(flat, (0, size - flat.shape[0]))


def _tree_unpack(buf, treedef, specs):
    leaves = [
        lax.slice(buf, (off,), (off + n,)).reshape(shape).astype(dtype)
        for (shape, dtype, off, n) in specs
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _batched_pack_spec(tree):
    """Like _tree_pack_spec but leaves keep a leading batch dim; specs are
    per-sample (shape[1:])."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    specs, off = [], 0
    for leaf in leaves:
        per = int(onp.prod(leaf.shape[1:])) if len(leaf.shape) > 1 else 1
        specs.append((tuple(leaf.shape[1:]), jnp.dtype(leaf.dtype), off, per))
        off += per
    return treedef, specs, off


def _batched_pack(tree, size: int):
    """Pack [B, ...] leaves into [B, size] fp32 wire buffer."""
    leaves = jax.tree_util.tree_leaves(tree)
    B = leaves[0].shape[0]
    flat = jnp.concatenate(
        [l.reshape(B, -1).astype(jnp.float32) for l in leaves], axis=1)
    return jnp.pad(flat, ((0, 0), (0, size - flat.shape[1])))


def _batched_unpack(buf, treedef, specs):
    B = buf.shape[0]
    leaves = [
        lax.slice(buf, (0, off), (B, off + n)).reshape((B,) + shape)
        .astype(dtype)
        for (shape, dtype, off, n) in specs
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


class HeteroPipeline:
    """GPipe pipeline with heterogeneous stages over the ``pp`` mesh axis.

    The reference has no pipeline parallelism at all (SURVEY.md §2.3); this
    is TPU-native surplus.  Design notes in the module docstring.

    Parameters
    ----------
    stage_fns : list of ``fn(stage_params, act, *extras) -> act``
        One per pipeline stage.  ``act`` is a pytree of arrays with leading
        (micro)batch dim; output boundary shapes may differ per stage.
        ``extras`` are per-microbatch side inputs (e.g. labels) delivered to
        every stage indexed by *that stage's* current microbatch.
    stage_params : list of pytrees (one per stage, structures may differ).
    mesh : Mesh with a ``pp`` axis of size ``len(stage_fns)`` (a ``dp``
        axis, if present, shards every batch dim).
    num_microbatches : microbatch count (must divide the global batch).
    example_x / example_extras : concrete or ShapeDtypeStruct trees used
        once with ``jax.eval_shape`` to derive the wire format.
    remat : rematerialise each stage call in backward (1F1B-like memory).
    """

    def __init__(self, stage_fns: Sequence[Callable],
                 stage_params: Sequence[Any], mesh: Mesh, *,
                 num_microbatches: int, example_x: Any,
                 example_extras: Tuple[Any, ...] = (),
                 axis_name: str = "pp", batch_axis: str = "dp",
                 remat: bool = False):
        n = len(stage_fns)
        assert n == len(stage_params), "one param tree per stage"
        assert mesh.shape.get(axis_name, 1) == n, (
            f"mesh axis '{axis_name}' (size {mesh.shape.get(axis_name, 1)}) "
            f"must equal number of stages ({n})")
        self.stage_fns = list(stage_fns)
        self.mesh = mesh
        self.axis_name = axis_name
        self.batch_axis = batch_axis if batch_axis in mesh.shape else None
        self.num_microbatches = num_microbatches
        self.n_stages = n
        self.remat = remat

        # ---- wire-exactness validation (satellite of the fp32 wire) -----
        # every stage's params and every activation boundary cross the
        # packed fp32 wire; refuse leaves it cannot carry exactly HERE,
        # at wire-spec derivation time, instead of silently rounding
        for j, p in enumerate(stage_params):
            _check_wire_tree(p, f"stage {j} param")
        _check_wire_tree(example_x, "pipeline input (example_x)",
                         allow_abstract_32=True)

        # ---- per-stage param pack specs (static) ------------------------
        self._p_specs = [_tree_pack_spec(p) for p in stage_params]
        self._p_size = max(s[2] for s in self._p_specs) or 1
        # leaf paths (keystr) per stage, aligned with pack-spec order, so
        # callers can locate a named leaf inside the packed buffer (used for
        # cross-stage weight tying)
        self._p_paths = [
            [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(p)[0]]
            for p in stage_params
        ]
        self.packed_params = self._pack_stage_params(stage_params)

        # ---- wire format: trace boundary shapes once --------------------
        dp = mesh.shape.get(batch_axis, 1) if self.batch_axis else 1
        leaves = jax.tree_util.tree_leaves(example_x)
        B = leaves[0].shape[0]
        assert B % (num_microbatches * dp) == 0, (
            f"batch {B} must divide num_microbatches*dp "
            f"({num_microbatches}x{dp})")
        mb = B // (num_microbatches * dp)  # per-device microbatch

        def _mb_struct(tree):
            return jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct((mb,) + tuple(l.shape[1:]),
                                               l.dtype), tree)

        self._example_extras = tuple(example_extras)
        extras_mb = tuple(_mb_struct(e) for e in example_extras)
        boundary = _mb_struct(example_x)
        self._b_specs = []           # input boundary spec per stage
        for j, fn in enumerate(self.stage_fns):
            self._b_specs.append(_batched_pack_spec(boundary))
            boundary = jax.eval_shape(fn, stage_params[j], boundary,
                                      *extras_mb)
            # computed inter-stage boundaries are abstract by
            # construction — wide-int outputs refuse loudly here
            _check_wire_tree(boundary, f"stage {j} output boundary")
        self._out_spec = _batched_pack_spec(boundary)   # last stage output
        self._w_size = max([s[2] for s in self._b_specs]
                           + [self._out_spec[2]])
        self._mb = mb
        self._apply = self._build_apply()

    # -- params -----------------------------------------------------------
    def _pack_stage_params(self, stage_params):
        bufs = [_tree_pack(p, self._p_size) for p in stage_params]
        stacked = jnp.stack(bufs)
        return jax.device_put(
            stacked, NamedSharding(self.mesh, P(self.axis_name, None)))

    def unpack_stage_params(self, packed=None) -> List[Any]:
        """[n_stages, P] buffer -> list of per-stage param pytrees."""
        if packed is None:
            packed = self.packed_params
        out = []
        for j, (treedef, specs, _) in enumerate(self._p_specs):
            out.append(_tree_unpack(packed[j], treedef, specs))
        return out

    def leaf_slice(self, stage: int, key: str) -> Tuple[int, int]:
        """(offset, size) of the named leaf inside stage ``stage``'s packed
        row.  ``key`` is the leaf's final pytree key (e.g. the dict key
        ``'embed.weight'``), matched exactly as the last path component."""
        want = f"['{key}']"
        for path, (shape, dtype, off, n) in zip(self._p_paths[stage],
                                                self._p_specs[stage][1]):
            if path == want or path.endswith(want):
                return off, n
        raise KeyError(f"no leaf matching {key!r} in stage {stage}: "
                       f"{self._p_paths[stage]}")

    def tie_grads(self, grads, ties):
        """Sum gradient slices of weight-tied leaves living on different
        stages and write the sum back to every member (Megatron-style tied
        embed/head).  ``grads`` is a [n_stages, P] packed cotangent;
        ``ties`` is an iterable of ((stage, key), (stage, key), ...)
        groups.  If the tied weights start equal and share one optimizer
        update rule, identical summed grads keep them exactly tied."""
        for group in ties:
            slices = [self.leaf_slice(s, k) for s, k in group]
            n = slices[0][1]
            assert all(sz == n for _, sz in slices), "tied leaves differ"
            total = sum(
                lax.dynamic_slice(grads, (s, off), (1, n))
                for (s, k), (off, _) in zip(group, slices))
            for (s, k), (off, _) in zip(group, slices):
                grads = lax.dynamic_update_slice(grads, total, (s, off))
        return grads

    # -- forward ----------------------------------------------------------
    def _build_apply(self):
        n = self.n_stages
        num_micro = self.num_microbatches
        W, mb = self._w_size, self._mb
        axis = self.axis_name
        b_specs, out_spec, p_specs = self._b_specs, self._out_spec, \
            self._p_specs
        stage_fns, remat = self.stage_fns, self.remat

        def device_fn(packed_params, x_wire, *extras):
            # packed_params [1, P] (this device's stage), x_wire
            # [num_micro, mb, W] (replicated over pp, sharded over dp)
            idx = lax.axis_index(axis)
            pbuf = packed_params[0]

            def run_stage(j, wire_in, extras_mb):
                params = _tree_unpack(pbuf, p_specs[j][0], p_specs[j][1])
                act = _batched_unpack(wire_in, b_specs[j][0], b_specs[j][1])
                out = stage_fns[j](params, act, *extras_mb)
                return _batched_pack(out, W)

            branches = [partial(run_stage, j) for j in range(n)]
            if remat:
                branches = [jax.checkpoint(b) for b in branches]

            def step(carry, t):
                buf, outs = carry
                feed = x_wire[jnp.clip(t, 0, num_micro - 1)]
                cur = jnp.where(idx == 0, feed, buf)
                # this device's current microbatch (clipped during
                # fill/drain; garbage steps are never recorded)
                mb_idx = jnp.clip(t - idx, 0, num_micro - 1)
                extras_mb = jax.tree_util.tree_map(
                    lambda e: e[mb_idx], extras)
                act = lax.switch(jnp.minimum(idx, n - 1), branches, cur,
                                 extras_mb)
                out_slot = t - (n - 1)
                outs = jnp.where(
                    (idx == n - 1) & (out_slot >= 0),
                    lax.dynamic_update_index_in_dim(
                        outs, act, jnp.clip(out_slot, 0, num_micro - 1), 0),
                    outs)
                perm = [(i, (i + 1) % n) for i in range(n)]
                buf = lax.ppermute(act, axis, perm=perm)
                return (buf, outs), None

            buf0 = jnp.zeros((mb, W), jnp.float32)
            outs0 = jnp.zeros((num_micro, mb, W), jnp.float32)
            (_, outs), _ = lax.scan(step, (buf0, outs0),
                                    jnp.arange(num_micro + n - 1))
            # deliver outputs from the last stage to all pp ranks so the
            # loss/grad is replicated over pp
            mask = (idx == n - 1).astype(outs.dtype)
            return lax.psum(outs * mask, axis)

        dp = self.batch_axis
        wire_spec = P(None, dp, None)
        extra_spec = P(None, dp)
        # shard_map is built ONCE (specs depend only on the extras structure
        # known at __init__) so eager pipe.apply calls hit jax's trace cache
        fn = shard_map(
            device_fn, mesh=self.mesh,
            in_specs=(P(axis, None), wire_spec)
            + tuple(jax.tree_util.tree_map(lambda _: extra_spec, e)
                    for e in self._example_extras),
            out_specs=wire_spec, check_vma=False)

        def apply(packed_params, x, *extras):
            # reshape [B, ...] -> [num_micro, mb*dp, ...] wire-packed
            leaves = jax.tree_util.tree_leaves(x)
            B = leaves[0].shape[0]
            gmb = B // num_micro    # global microbatch (pre-dp-shard)

            def to_micro(tree):
                return jax.tree_util.tree_map(
                    lambda l: l.reshape((num_micro, gmb) + l.shape[1:]),
                    tree)

            xm = to_micro(x)
            x_wire = jax.vmap(lambda t: _batched_pack(t, W))(xm)
            extras_m = tuple(to_micro(e) for e in extras)
            out_wire = fn(packed_params, x_wire, *extras_m)
            out = jax.vmap(
                lambda t: _batched_unpack(t, out_spec[0], out_spec[1])
            )(out_wire)
            # merge microbatch dim back into batch
            return jax.tree_util.tree_map(
                lambda l: l.reshape((num_micro * l.shape[1],) + l.shape[2:]),
                out)

        return apply

    def apply(self, packed_params, x, *extras):
        """Run the full pipeline: ``x`` [B, ...] -> last-stage outputs
        [B, ...] (microbatching is internal).  Differentiable w.r.t.
        ``packed_params``."""
        return self._apply(packed_params, x, *extras)


# ---------------------------------------------------------------------------
# Gluon adapter: the pipeline as a trainable Block in the one donated step
# ---------------------------------------------------------------------------

_PIPELINE_BLOCK_CLS = None


def _pipeline_block_cls():
    """Build the PipelineBlock class lazily: gluon imports here (not at
    module import) keep ``mxnet_tpu.parallel`` free of an import cycle
    through the gluon package."""
    global _PIPELINE_BLOCK_CLS
    if _PIPELINE_BLOCK_CLS is not None:
        return _PIPELINE_BLOCK_CLS

    from .. import autograd as _ag
    from ..context import current_context
    from ..gluon.block import Block, jax_bridge
    from ..gluon.parameter import Parameter
    from ..ndarray import NDArray
    from ..ndarray.ndarray import _wrap

    class PipelineBlock(Block):
        """A :class:`HeteroPipeline` as a gluon block: ONE trainable
        parameter — the packed ``[n_stages, P]`` fp32 stage buffer —
        so ``Trainer.compile_step`` traces the pipeline's scan-internal
        microbatch schedule into the single donated step program (one
        dispatch per step; N+1 per window under gradient accumulation).

        The packed parameter is named ``pp_stages``: under a mesh with a
        real ``pp`` axis, ``spmd.param_spec`` places it ``P('pp', None)``
        (device *i* holds stage *i*'s weights) and the fused optimizer
        updates it elementwise in packed space — exact, since packing is
        a concat of fp32 leaves and padding sees zero grads.  Gradients
        of weight-tied leaves (``pipe.tied``) are summed across stages
        via :meth:`compiled_grad_transform`, which the TrainStep applies
        inside the compiled program right after the vjp.

        On the eager tape (compiled-step fallback) the forward routes
        through :func:`gluon.block.jax_bridge`, so autograd still
        differentiates the shard_map schedule; batch shape is fixed to
        the wire derived at ``HeteroPipeline.__init__``.
        """

        def __init__(self, pipe: HeteroPipeline):
            super().__init__()
            self._pipe = pipe
            packed = pipe.packed_params
            ctx = current_context()
            self.pp_stages = Parameter(
                "pp_stages", shape=tuple(packed.shape), dtype="float32")
            # the value IS the packed buffer — install it directly
            # (the name-pattern default initializer doesn't know it)
            self.pp_stages._load_init(_wrap(packed, ctx), ctx=[ctx])

        @property
        def pipe(self) -> HeteroPipeline:
            return self._pipe

        def unpack_stage_params(self):
            """Per-stage param pytrees from the CURRENT parameter value
            (``pipe.packed_params`` keeps only the initial buffer)."""
            return self._pipe.unpack_stage_params(
                self.pp_stages.data()._data)

        def compiled_grad_transform(self, named_grads):
            """TrainStep grad hook: sum tied-leaf gradient slices across
            stages (Megatron-style tied embed/head) on the packed
            cotangent.  Linear, so per-microbatch application under
            accumulation equals application on the window sum."""
            ties = getattr(self._pipe, "tied", ())
            if not ties:
                return named_grads
            out = dict(named_grads)
            for name, g in named_grads.items():
                if name == "pp_stages" or name.endswith(".pp_stages"):
                    out[name] = self._pipe.tie_grads(g, ties)
            return out

        def forward(self, x, *extras):
            w = self.pp_stages.data()
            if _ag.is_recording() and not isinstance(
                    w._data, jax.core.Tracer):
                return jax_bridge(self._pipe.apply, w, x,
                                  *[e for e in extras])
            ctx = x.ctx if isinstance(x, NDArray) else current_context()
            raw = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                   for a in (x,) + tuple(extras)]
            out = self._pipe.apply(w._data, *raw)
            return jax.tree_util.tree_map(lambda l: _wrap(l, ctx), out)

    _PIPELINE_BLOCK_CLS = PipelineBlock
    return _PIPELINE_BLOCK_CLS


def __getattr__(name):
    if name == "PipelineBlock":
        return _pipeline_block_cls()
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
