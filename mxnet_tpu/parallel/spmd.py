"""Pod-scale SPMD data parallelism: ``kvstore='tpu'`` as mesh sharding.

The reference scales data-parallel training through KVStore push/pull
(``src/kvstore/``): the Trainer pushes gradients, a comm backend
(NCCL rings / ps-lite servers) reduces them, and the workers pull the
result — a host-driven collective standing OUTSIDE the computation.
TPU-native, the same contract is a *sharding*: parameters and optimizer
state replicate across a named ``jax.sharding.Mesh`` ``'dp'`` axis, the
batch shards over it, and the gradient all-reduce becomes an ICI-native
collective the XLA SPMD partitioner schedules INSIDE the one donated
train-step program (arXiv:2301.13062 — collectives the compiler sees can
overlap backward; arXiv:2008.01040 — padding/placement is where TPU
performance lives).  ``Trainer(..., kvstore='tpu')`` +
``Trainer.compile_step`` route through here with zero user-code changes.

Beyond pure data parallelism the same one-program contract covers the
model-parallel axes: an ``fsdp`` mesh axis shards parameters and
optimizer state (ZeRO-3 style — :func:`param_spec` picks each leaf's
largest evenly-divisible dim, indivisible leaves replicate LOUDLY via
the ``sharding.legalize_refusal`` idiom), and a ``tp`` axis carries
``sharding.constraint`` annotations from model code through the traced
step.  In every case the scatter/gather/all-reduce schedule belongs to
the XLA SPMD partitioner INSIDE the one donated program — still 1
dispatch/step, 0 retraces, 0 host-side cross-device copies.

This module owns the placement plumbing shared by ``cached_step``
(training), ``engine.DevicePrefetcher`` (input staging), ``serving``
(replicated inference) and the DataLoader (per-process sharded
sampling):

- :func:`mesh_for_store` — resolve the mesh for a kvstore type under
  the ``MXNET_SPMD_MESH`` knob (``auto`` = every visible device on the
  ``'dp'`` axis; an int = that many devices; ``off`` disables;
  ``dp=4,fsdp=2`` axis-spec strings go through :func:`mesh.make_mesh`
  — the compiled step shards the batch over ``'dp'`` only, params/
  optimizer state over ``'fsdp'``, and leaves ``'tp'`` placement to
  model-code :func:`~.sharding.constraint` calls).
- :func:`put_batch` — stage one batch leaf with the batch
  ``NamedSharding`` (site ``spmd.put``, shared retry policy).  Under
  multi-controller the host array is this process's shard of the
  GLOBAL batch (the DataLoader ``num_shards`` contract) and the global
  array assembles via ``jax.make_array_from_process_local_data``.
  A batch axis the mesh cannot divide evenly is REPLICATED instead —
  loudly (:func:`replicated_batch_count` + a warning), never an error
  mid-step and never silent.
- :func:`ensure_placed` — idempotent replicated placement for
  parameters/optimizer state; every actual device_put is counted
  (:func:`reshard_count`) so the dispatch-budget gate can pin
  "0 host-side cross-device copies in steady state".
"""
from __future__ import annotations

import re as _re
import threading
import warnings
from typing import Optional, Tuple

import jax
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import config as _config
from .. import faults as _faults
from .mesh import make_mesh

__all__ = ["DATA_AXIS", "MODEL_AXIS", "TENSOR_AXIS", "PIPE_AXIS",
           "EXPERT_AXIS", "model_axes_active", "mesh_for_store",
           "resolve_mesh", "batch_sharding", "replicated", "batch_spec_for",
           "param_spec", "param_sharding", "put_batch", "ensure_placed",
           "mesh_key", "reshard_count", "replicated_batch_count",
           "record_layout", "param_bytes_per_device",
           "opt_bytes_per_device", "reset_counters"]

# the canonical data-parallel axis (mesh.AXIS_NAMES's 'dp'): the KVStore
# axis — gradients all-reduce over it, the batch shards over it
DATA_AXIS = "dp"
# the parameter-sharding axis (ZeRO/FSDP): params + optimizer state
# shard over it, the batch does NOT
MODEL_AXIS = "fsdp"
# the tensor-parallel axis: placement is model-code's move (via
# sharding.constraint / a ShardingPlan), never implied by this module
TENSOR_AXIS = "tp"
# the pipeline axis: HeteroPipeline's packed [n_stages, P] stage buffer
# shards dim 0 over it (device i holds stage i's weights); matched BY
# NAME in param_spec — the packed parameter is canonically 'pp_stages'
PIPE_AXIS = "pp"
# the expert-parallel axis: MoE expert weights ([E, ...] leaves under an
# 'expert.' structural prefix) shard dim 0 over it
EXPERT_AXIS = "ep"

# name-aware placement rules (param_spec): structural parameter names
# matching these regexes take first-class-axis placement before the
# shape-only FSDP rule is consulted
_PIPE_PACKED_RE = _re.compile(r"(^|\.)pp_stages$")
_EXPERT_RE = _re.compile(r"(^|\.)expert\.")

# kvstore types whose reduce is the ICI-collective mesh path.  dist/
# ps-lite-style stores stay host-driven and keep the eager fallback.
_MESH_STORES = ("tpu", "nccl")

from .. import telemetry as _telemetry

_lock = threading.Lock()
# param/state leaves actually moved by ensure_placed (first-step placement
# is expected; a steady-state bump is a silent cross-device copy — the
# budget gate pins it at 0 after warmup)
_RESHARD = _telemetry.counter(
    "spmd.reshard",
    "param/state leaves actually moved by ensure_placed (first-step "
    "placement expected; a steady-state bump is a silent cross-device "
    "copy — the budget gate pins it at 0 after warmup)")
# batches replicated because the 'dp' axis could not divide the batch
# axis evenly (correct, but no scale-out for that step — loud by contract)
_REPLICATED_BATCH = _telemetry.counter(
    "spmd.replicated_batch",
    "batches replicated because the 'dp' axis could not divide the "
    "batch axis evenly (correct but no scale-out that step)")
_WARNED_SHAPES: set = set()

# per-device memory accounting: the byte footprint of the CURRENT
# parameter / optimizer-state layout on ONE device, computed from each
# placed leaf's actual sharding (shard_shape) — so an fsdp-sharded
# layout reads ~1/N of the replicated one.  Recorded by the TrainStep
# warmup (record_layout), surfaced as computed gauges in
# telemetry.report().
_LAYOUT_BYTES = {"param": 0, "opt": 0}


def _leaf_bytes_per_device(arr) -> int:
    """One leaf's bytes on ONE device: the shard shape (under its actual
    sharding) × itemsize.  Unplaced/host leaves count their full size."""
    shape = tuple(int(s) for s in getattr(arr, "shape", ()))
    sh = getattr(arr, "sharding", None)
    if sh is not None:
        try:
            shape = tuple(int(s) for s in sh.shard_shape(shape))
        except Exception:
            pass
    n = 1
    for s in shape:
        n *= s
    itemsize = getattr(getattr(arr, "dtype", None), "itemsize", 4)
    return n * int(itemsize)


def record_layout(param_leaves, opt_leaves) -> None:
    """Record the per-device byte footprint of the placed parameter and
    optimizer-state layout (TrainStep warmup calls this after
    placement; single-chip layouts record their full size)."""
    p = sum(_leaf_bytes_per_device(a) for a in param_leaves)
    o = sum(_leaf_bytes_per_device(a) for a in opt_leaves)
    with _lock:
        _LAYOUT_BYTES["param"] = int(p)
        _LAYOUT_BYTES["opt"] = int(o)


def param_bytes_per_device() -> int:
    """Bytes of parameters resident on ONE device under the current
    layout (gauge ``spmd.param_bytes_per_device``)."""
    return int(_LAYOUT_BYTES["param"])


def opt_bytes_per_device() -> int:
    """Bytes of optimizer state resident on ONE device under the
    current layout (gauge ``spmd.opt_bytes_per_device``)."""
    return int(_LAYOUT_BYTES["opt"])


_telemetry.gauge_fn(
    "spmd.param_bytes_per_device", param_bytes_per_device,
    "bytes of parameters resident on one device under the current "
    "layout (replicated: the full model; fsdp-sharded: ~1/N)")
_telemetry.gauge_fn(
    "spmd.opt_bytes_per_device", opt_bytes_per_device,
    "bytes of optimizer state resident on one device under the current "
    "layout (replicated: the full state; fsdp-sharded: ~1/N)")


def reshard_count() -> int:
    return int(_RESHARD.value)


def replicated_batch_count() -> int:
    return int(_REPLICATED_BATCH.value)


def reset_counters() -> None:
    _RESHARD.reset()
    _REPLICATED_BATCH.reset()
    with _lock:
        _LAYOUT_BYTES["param"] = 0
        _LAYOUT_BYTES["opt"] = 0


# ---------------------------------------------------------------------------
# mesh resolution (MXNET_SPMD_MESH)
# ---------------------------------------------------------------------------

def _admitted_devices():
    """Visible devices minus the sentinel's active quarantine list (a
    corrupt replica or a heartbeat-suspected rank persisted by a prior
    incarnation): the restart-time exclusion that re-resolves the mesh
    WITHOUT the suspect device.  Excluding everything would leave no
    mesh to train on — that degenerate list is ignored loudly."""
    devices = jax.devices()
    from .. import sentinel as _sentinel

    q = _sentinel.active_quarantine()
    if q is None:
        return devices
    kept = q.filter_devices(devices)
    if not kept:
        warnings.warn(
            "every visible device is quarantined "
            f"(entries: {q.entries()}); ignoring the quarantine list "
            "for mesh resolution", stacklevel=3)
        return devices
    if len(kept) < len(devices):
        excluded = sorted(d.id for d in devices if d not in kept)
        _log_quarantine_exclusion(excluded, q)
    return kept


def _log_quarantine_exclusion(excluded, q) -> None:
    from ..log import get_logger

    get_logger("mxnet_tpu.spmd").warning(
        "mesh resolution excludes quarantined device(s) %s "
        "(quarantine: %s)", excluded, q.entries())
    _telemetry.event("corruption", "spmd.quarantine_excluded",
                     devices=excluded)


def resolve_mesh(spec: Optional[str] = None) -> Optional[Mesh]:
    """Resolve ``MXNET_SPMD_MESH`` (or an explicit spec string) into a
    data-parallel mesh, or ``None`` when SPMD is off.

    - ``auto`` (default): every visible device on the ``'dp'`` axis;
      a single-device world resolves to ``None`` (the plain single-chip
      compiled step — no behavior change off-pod).
    - ``0`` / ``off`` / ``none``: disabled.
    - ``<int>``: that many devices on ``'dp'`` (``1`` gives a real
      1-device mesh — the parity oracle for sharded-vs-single tests).
    - ``dp=4,fsdp=2`` style: axis spec via :func:`mesh.make_mesh`.  The
      compiled step shards the batch over ``'dp'`` ONLY; an ``fsdp``
      axis shards params + optimizer state (:func:`param_spec`); a
      ``tp`` axis is left to model-code ``sharding.constraint`` calls,
      which resolve against this mesh inside the traced step.  Axes
      compose on one mesh (``dp=2,fsdp=2,tp=2`` needs 8 devices).

    Every form resolves over the ADMITTED device set: devices (or whole
    ranks) in the sentinel's persisted quarantine list are excluded, so
    a restart after a localized corruption or a hung host re-places
    onto a mesh without the suspect (the PR-11 topology-change
    machinery, triggered automatically).
    """
    raw = spec if spec is not None else _config.get("MXNET_SPMD_MESH")
    raw = (raw or "auto").strip().lower()
    if raw in ("0", "off", "none", "disabled"):
        return None
    devices = _admitted_devices()
    if raw in ("auto", ""):
        if len(devices) < 2:
            return None
        return make_mesh({DATA_AXIS: len(devices)}, devices)
    if raw.isdigit():
        n = int(raw)
        if n < 1:
            return None
        if n > len(devices):
            raise ValueError(
                f"MXNET_SPMD_MESH={n} needs {n} devices, only "
                f"{len(devices)} visible")
        return make_mesh({DATA_AXIS: n}, devices[:n])
    axes = {}
    for part in raw.split(","):
        if not part.strip():
            continue
        k, _, v = part.partition("=")
        axes[k.strip()] = int(v)
    if DATA_AXIS not in axes:
        raise ValueError(
            f"MXNET_SPMD_MESH={raw!r} must name the '{DATA_AXIS}' axis "
            "(e.g. 'dp=8'), or be 'auto'/'off'/an integer")
    return make_mesh(axes, devices)


def mesh_for_store(kv_type: Optional[str]) -> Optional[Mesh]:
    """The mesh a :class:`~mxnet_tpu.cached_step.TrainStep` should trace
    under for a given kvstore type: the resolved ``MXNET_SPMD_MESH``
    mesh for the ICI-collective stores (``'tpu'``/``'nccl'``), ``None``
    (single-chip path) for everything else."""
    if kv_type is None or kv_type.lower() not in _MESH_STORES:
        return None
    return resolve_mesh()


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------

def replicated(mesh: Mesh) -> NamedSharding:
    """Params / optimizer state / scalars: one replica per mesh device
    (the KVStore broadcast contract)."""
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """The canonical batch placement: axis 0 split over ``'dp'`` — and
    ONLY ``'dp'``; a multi-axis mesh (``fsdp``/``tp``) never shards the
    batch over its model axes."""
    return NamedSharding(mesh, PartitionSpec(DATA_AXIS))


def param_spec(shape: Tuple[int, ...], mesh: Mesh,
               min_size: Optional[int] = None,
               name: Optional[str] = None) -> PartitionSpec:
    """Placement rule for one parameter / optimizer-state leaf.

    Name-aware first-class-axis rules run first (``name`` is the
    structural parameter name when the caller knows it):

    - ``pp_stages`` (HeteroPipeline's packed ``[n_stages, P]`` stage
      buffer) → ``P('pp', None)`` when the mesh's ``pp`` axis equals the
      stage count — device *i* holds stage *i*'s packed weights;
    - ``expert.*`` leaves (MoE expert weights ``[E, ...]``) →
      ``P('ep')`` on dim 0 when ``ep`` divides the expert count.

    Otherwise the FSDP/ZeRO rule: shard the LARGEST dim the ``'fsdp'``
    axis divides evenly.  Leaves below ``min_size`` elements
    (``MXNET_FSDP_MIN_SIZE``) stay replicated — sharding a LayerNorm
    bias buys nothing and costs an all-gather.  A large leaf NO dim of
    which divides the axis degrades to replication LOUDLY via the
    ``sharding.legalize_refusal`` idiom (counted + warned once per
    shape), never an error mid-warmup."""
    if name and shape:
        n_pp = int(mesh.shape.get(PIPE_AXIS, 1))
        if n_pp > 1 and _PIPE_PACKED_RE.search(name) \
                and shape[0] == n_pp:
            return PartitionSpec(PIPE_AXIS,
                                 *([None] * (len(shape) - 1)))
        n_ep = int(mesh.shape.get(EXPERT_AXIS, 1))
        if n_ep > 1 and _EXPERT_RE.search(name) \
                and shape[0] % n_ep == 0:
            return PartitionSpec(EXPERT_AXIS,
                                 *([None] * (len(shape) - 1)))
    if min_size is None:
        min_size = int(_config.get("MXNET_FSDP_MIN_SIZE"))
    n = int(mesh.shape.get(MODEL_AXIS, 1))
    if n <= 1 or not shape:
        return PartitionSpec()
    size = 1
    for s in shape:
        size *= int(s)
    if size < min_size:
        return PartitionSpec()
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if shape[i] % n == 0:
            spec = [None] * len(shape)
            spec[i] = MODEL_AXIS
            return PartitionSpec(*spec)
    # no dim divides the axis: the loudly-replicated fallback
    from .sharding import _legalize

    return _legalize(PartitionSpec(MODEL_AXIS), tuple(shape), mesh,
                     loud=True)


def param_sharding(shape: Tuple[int, ...], mesh: Mesh,
                   name: Optional[str] = None) -> NamedSharding:
    """The ``NamedSharding`` a param/state leaf of ``shape`` takes on
    ``mesh``: :func:`param_spec` (name-aware pp/ep rules, then the
    ``fsdp`` shape rule), replicated otherwise."""
    return NamedSharding(mesh, param_spec(shape, mesh, name=name))


def model_axes_active(mesh: Mesh) -> bool:
    """True when any model-side placement axis (``fsdp``/``pp``/``ep``)
    is real (> 1) on ``mesh`` — the gate for per-leaf name/shape-aware
    parameter placement in the compiled step."""
    return any(int(mesh.shape.get(a, 1)) > 1
               for a in (MODEL_AXIS, PIPE_AXIS, EXPERT_AXIS))


def batch_spec_for(shape: Tuple[int, ...], mesh: Mesh) -> PartitionSpec:
    """Legalized batch spec for one leaf: ``P('dp')`` when the batch
    axis divides evenly, ``P()`` (replicated, counted + warned once per
    shape) otherwise.  Never raises mid-step."""
    n = int(mesh.shape.get(DATA_AXIS, 1))
    if n <= 1 or not shape:
        return PartitionSpec()      # scalars replicate, silently
    if shape[0] % n != 0:
        with _lock:
            _REPLICATED_BATCH.inc()
            key = (tuple(shape), n)
            if key not in _WARNED_SHAPES:
                _WARNED_SHAPES.add(key)
                warnings.warn(
                    f"SPMD batch axis {shape[0]} is not divisible by the "
                    f"{n}-way '{DATA_AXIS}' mesh axis; this input is "
                    "REPLICATED for correctness (no data-parallel speedup "
                    "for it). Pad the batch (e.g. "
                    "DataLoader(last_batch='pad')) or pick a divisible "
                    "batch size.", stacklevel=3)
        return PartitionSpec()
    return PartitionSpec(DATA_AXIS)


def mesh_key(mesh: Optional[Mesh]):
    """Hashable program-cache key component: the mesh's axes and exact
    device set (a different topology must never reuse a program)."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names),
            tuple(int(s) for s in mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

def _equivalently_placed(arr, sharding: NamedSharding) -> bool:
    cur = getattr(arr, "sharding", None)
    if cur is None:
        return False
    # uncommitted arrays sit on the default device only by accident —
    # they must be pinned to the mesh explicitly once
    if not getattr(arr, "committed", True):
        return False
    try:
        return cur.is_equivalent_to(sharding, arr.ndim)
    except Exception:
        return False


def ensure_placed(arr: jax.Array, sharding: NamedSharding) -> jax.Array:
    """Idempotent placement: return ``arr`` untouched when it already
    carries an equivalent sharding, else ``device_put`` it (counted in
    :func:`reshard_count` — steady state must not pay this)."""
    if _equivalently_placed(arr, sharding):
        return arr
    _RESHARD.inc()
    return jax.device_put(arr, sharding)


def _put_batch_once(arr, sharding: NamedSharding):
    if jax.process_count() > 1:
        # multi-controller: ``arr`` is this process's contiguous shard of
        # the global batch (the DataLoader num_shards contract); assemble
        # the global jax.Array from per-process local data
        # graftlint: disable=host-sync -- ``arr`` is the HOST batch shard
        # being staged to device, not a device array read back
        return jax.make_array_from_process_local_data(
            sharding, onp.asarray(arr))
    return jax.device_put(arr, sharding)


def put_batch(arr, mesh: Mesh):
    """Stage one batch leaf onto the mesh with the legalized batch
    sharding (already-staged leaves — the DevicePrefetcher path — pass
    through untouched).  A transient transfer failure retries under the
    shared policy (site ``spmd.put``), mirroring ``engine.prefetch``."""
    shape = tuple(getattr(arr, "shape", ()))
    sharding = NamedSharding(mesh, batch_spec_for(shape, mesh))
    if isinstance(arr, jax.Array) and _equivalently_placed(arr, sharding):
        return arr
    return _faults.retry_call(_put_batch_once, arr, sharding,
                              site="spmd.put")
