"""Sharding plans: parameter-name patterns → PartitionSpec.

The reference's distribution story is value-level (KVStore decides where each
parameter lives, src/kvstore/kvstore_local.h key grouping).  Here placement is
declarative: a ``ShardingPlan`` is an ordered rule list matched against the
structural parameter name (the same names ``Block.collect_params`` produces),
yielding a ``PartitionSpec``.  Rules that don't divide the actual shape fall
back to replication on the offending axis — the analog of the reference's
big-array splitting guard (``MXNET_KVSTORE_BIGARRAY_BOUND``,
src/kvstore/kvstore_dist.h:44) where non-conforming tensors degrade
gracefully instead of erroring.
"""
from __future__ import annotations

import re
import warnings
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["ShardingPlan", "fsdp_plan", "tensor_parallel_plan",
           "expert_parallel_plan", "replicated_plan", "shard_array",
           "constraint", "legalize_refusal_count",
           "reset_legalize_refusals"]

Spec = PartitionSpec

# legalization observability: every spec dim REFUSED (replicated) because
# the shape could not divide the mesh axis evenly.  Refusal is the
# mid-trace-safe half of "pad-or-refuse": a traced value's shape is
# frozen, so padding belongs to the batch boundary (DataLoader
# last_batch='pad', serving buckets) — here the offending dim degrades
# to replication, counted and (on the constraint path) warned.
from .. import telemetry as _telemetry  # noqa: E402

_LEGALIZE_REFUSAL = _telemetry.counter(
    "sharding.legalize_refusal",
    "spec dims refused (degraded to replication) because the shape "
    "could not divide the mesh axis evenly")
_WARNED_REFUSALS: set = set()


def legalize_refusal_count() -> int:
    return int(_LEGALIZE_REFUSAL.value)


def reset_legalize_refusals() -> None:
    _LEGALIZE_REFUSAL.reset()


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, (tuple, list)):
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axes]


def _legalize(spec: PartitionSpec, shape: Tuple[int, ...], mesh: Mesh,
              loud: bool = False) -> PartitionSpec:
    """Drop sharding on dims the shape can't evenly divide, and on axes the
    mesh doesn't have.  Divisibility refusals are counted
    (:func:`legalize_refusal_count`) and, with ``loud=True`` (the
    :func:`constraint` path), warned once per (shape, spec) — degrading a
    constraint must never be silent, and erroring mid-trace is worse."""
    out = []
    padded = (tuple(spec) + (None,) * len(shape))[: len(shape)]
    for i, axes in enumerate(padded):
        if axes is None:
            out.append(None)
            continue
        ax_tuple = axes if isinstance(axes, (tuple, list)) else (axes,)
        ax_tuple = tuple(a for a in ax_tuple if a in mesh.shape)
        if not ax_tuple:
            out.append(None)
            continue
        n = _axis_size(mesh, ax_tuple)
        if n == 1:
            out.append(None)
        elif shape[i] % n != 0:
            _LEGALIZE_REFUSAL.inc()
            if loud:
                key = (tuple(shape), i, ax_tuple, n)
                if key not in _WARNED_REFUSALS:
                    _WARNED_REFUSALS.add(key)
                    warnings.warn(
                        f"sharding constraint refused on dim {i} of shape "
                        f"{tuple(shape)}: {shape[i]} is not divisible by "
                        f"the {n}-way mesh axis {ax_tuple} — dim "
                        "REPLICATED instead (pad the value at the batch "
                        "boundary, e.g. DataLoader(last_batch='pad') or "
                        "a bucket grid, to shard it)", stacklevel=4)
            out.append(None)
        else:
            out.append(ax_tuple[0] if len(ax_tuple) == 1 else ax_tuple)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


class ShardingPlan:
    """Ordered (regex, PartitionSpec) rules; first match wins."""

    def __init__(self, rules: Sequence[Tuple[str, PartitionSpec]] = (),
                 default: PartitionSpec = PartitionSpec()):
        self.rules: List[Tuple[re.Pattern, PartitionSpec]] = [
            (re.compile(pat), spec) for pat, spec in rules
        ]
        self.default = default

    def add(self, pattern: str, spec: PartitionSpec) -> "ShardingPlan":
        self.rules.append((re.compile(pattern), spec))
        return self

    def extend(self, other: "ShardingPlan") -> "ShardingPlan":
        self.rules.extend(other.rules)
        return self

    def spec_for(self, name: str, shape: Tuple[int, ...], mesh: Mesh) -> PartitionSpec:
        for pat, spec in self.rules:
            if pat.search(name):
                return _legalize(spec, shape, mesh)
        return _legalize(self.default, shape, mesh)

    def shard(self, name: str, arr: jax.Array, mesh: Mesh) -> jax.Array:
        spec = self.spec_for(name, tuple(arr.shape), mesh)
        return jax.device_put(arr, NamedSharding(mesh, spec))

    def shard_tree(self, params: Dict[str, jax.Array], mesh: Mesh
                   ) -> Dict[str, jax.Array]:
        return {n: self.shard(n, a, mesh) for n, a in params.items()}

    def specs_tree(self, params: Dict[str, jax.Array], mesh: Mesh
                   ) -> Dict[str, PartitionSpec]:
        return {n: self.spec_for(n, tuple(a.shape), mesh)
                for n, a in params.items()}


def replicated_plan() -> ShardingPlan:
    """Pure data parallelism: every parameter replicated (the reference's
    KVStore broadcast semantics, comm.h Broadcast)."""
    return ShardingPlan()


def fsdp_plan(axis: str = "fsdp", min_size: int = 1024) -> ShardingPlan:
    """ZeRO-3 style: shard every parameter's largest dim over ``axis``.

    Implemented as a dynamic plan (shape-dependent), so spec_for is
    overridden rather than rule-driven.
    """

    class _FSDP(ShardingPlan):
        def spec_for(self, name, shape, mesh):
            for pat, spec in self.rules:
                if pat.search(name):
                    return _legalize(spec, shape, mesh)
            if not shape:
                return PartitionSpec()
            n = mesh.shape.get(axis, 1)
            size = 1
            for s in shape:
                size *= s
            if n == 1 or size < min_size:
                return PartitionSpec()
            # shard the largest evenly-divisible dim
            order = sorted(range(len(shape)), key=lambda i: -shape[i])
            for i in order:
                if shape[i] % n == 0:
                    spec = [None] * len(shape)
                    spec[i] = axis
                    return PartitionSpec(*spec)
            return PartitionSpec()

    return _FSDP()


def expert_parallel_plan(axis: str = "ep") -> ShardingPlan:
    """Expert parallelism (parallel/moe.py): expert weights — ``[E, ...]``
    leaves under an ``expert.`` structural prefix — shard dim 0 over
    ``axis``; everything else (gate, dense trunk) replicates.  The plan
    form of the name-aware ``spmd.param_spec`` ep rule, for callers that
    place params through a ShardingPlan."""
    return ShardingPlan([
        (r"(^|\.)expert\..*", PartitionSpec(axis)),
        (r".*", PartitionSpec()),
    ])


def tensor_parallel_plan(axis: str = "tp") -> ShardingPlan:
    """Megatron-style transformer sharding by structural-name convention:

    - qkv / gate+up projections: shard output features (column parallel)
    - attention output / MLP down projection: shard input features (row
      parallel) — XLA inserts the all-reduce after the matmul
    - embeddings: shard vocab dim
    - norms / biases of row-parallel layers: replicated
    """
    return ShardingPlan([
        (r"(qkv|query|key|value|q_proj|k_proj|v_proj|ffn_1|fc1|up|gate|inter)"
         r".*weight$", Spec(axis, None)),
        (r"(qkv|query|key|value|q_proj|k_proj|v_proj|ffn_1|fc1|up|gate|inter)"
         r".*bias$", Spec(axis)),
        (r"(out_proj|o_proj|proj|ffn_2|fc2|down|output).*weight$",
         Spec(None, axis)),
        (r"embed.*weight$", Spec(axis, None)),
    ])


def shard_array(arr: jax.Array, mesh: Mesh, spec: PartitionSpec) -> jax.Array:
    return jax.device_put(arr, NamedSharding(mesh, _legalize(spec, tuple(arr.shape), mesh)))


def _ambient_mesh():
    """The mesh jax itself already has in scope — works INSIDE a traced
    fn, where no explicit mesh was threaded through: first the classic
    ``with mesh:`` context (thread_resources physical mesh — what
    ``mesh_scope`` enters), then the abstract-mesh ambient
    (``jax.sharding.get_abstract_mesh``).
    Returns ``None`` when there is genuinely no mesh anywhere."""
    try:
        from jax._src import mesh as _jm

        pm = _jm.thread_resources.env.physical_mesh
        if pm is not None and not getattr(pm, "empty", True):
            return pm
    except Exception:
        pass
    ambient = jax.sharding.get_abstract_mesh()
    if ambient is not None and getattr(ambient, "shape", None):
        return ambient
    return None


def constraint(x, spec: Union[PartitionSpec, Sequence], mesh: Optional[Mesh] = None):
    """``lax.with_sharding_constraint`` that keeps model code
    mesh-agnostic and mid-trace-safe:

    - ``mesh=None`` resolves the ENCLOSING mesh — ``mesh_scope``'s
      current mesh, the ``with mesh:`` jax context, or the abstract
      ambient mesh — so a constraint inside a traced fn never needs the
      mesh threaded through the call stack.  No mesh anywhere: no-op.
    - The spec is legalized against the value's (static) shape before it
      reaches XLA: a dim the mesh axis cannot divide evenly is REFUSED
      (replicated) loudly — warned + counted in
      :func:`legalize_refusal_count` — instead of erroring mid-trace.
      Padding is the caller's move, at the batch boundary.
    - A spec naming an axis the mesh does not have still raises — a
      typo'd axis must not silently drop the constraint.
    - NDArray wrappers pass through transparently (unwrapped,
      constrained, re-wrapped), so model code can pin an activation or
      weight layout inside a hybridizable ``forward`` — the compiled
      train step traces and dispatches inside the mesh context, so the
      annotation reaches the XLA partitioner (the tensor-parallel
      path: ``constraint(h, ('dp', 'tp'))`` on a hidden activation).
    """
    data = getattr(x, "_data", None)
    if data is not None and hasattr(x, "ctx"):
        from ..ndarray import ndarray as _ndmod

        out = constraint(data, spec, mesh)
        return _ndmod._wrap(out, x.ctx, type(x))
    if mesh is None:
        from .mesh import current_mesh

        mesh = current_mesh()
    if mesh is None:
        mesh = _ambient_mesh()
    if mesh is None or not getattr(mesh, "shape", None):
        return x  # no mesh anywhere: mesh-agnostic no-op
    spec = spec if isinstance(spec, PartitionSpec) else PartitionSpec(*spec)
    # canonical axes (mesh.AXIS_NAMES) the mesh does not carry are
    # size-1 by convention and legalize away silently — a model
    # annotated for 'tp' still runs on a pure-dp mesh (the parity
    # oracle).  A NON-canonical name is a typo and must raise.
    from .mesh import AXIS_NAMES

    known = set(mesh.shape) | set(AXIS_NAMES)
    for axes in tuple(spec):
        for a in (axes if isinstance(axes, (tuple, list)) else (axes,)):
            if a is not None and a not in known:
                raise ValueError(
                    f"sharding constraint names axis {a!r} but the mesh "
                    f"in scope only has {sorted(mesh.shape)} (canonical "
                    f"axes {AXIS_NAMES} legalize away when absent) — a "
                    "typo'd axis must not silently drop the constraint")
    lspec = _legalize(spec, tuple(getattr(x, "shape", ())), mesh, loud=True)
    if isinstance(mesh, Mesh):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, lspec))
    # abstract ambient mesh: a bare PartitionSpec resolves against it
    return jax.lax.with_sharding_constraint(x, lspec)
