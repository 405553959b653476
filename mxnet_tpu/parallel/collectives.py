"""Named collectives over mesh axes.

The reference's communication layer is imperative: CommCPU/CommDevice reduce
buffers (src/kvstore/comm.h:104-556), KVStoreNCCL issues ncclReduce/Bcast
(src/kvstore/kvstore_nccl.h), ps-lite RPCs for multi-node.  On TPU these are
XLA collectives over ICI/DCN, expressed with ``jax.lax`` primitives inside
``shard_map``/``pjit`` regions.  This module gives them KVStore-flavoured
names so higher layers (kvstore='tpu'/'dist', ring attention, MoE dispatch)
read like the survey's component inventory.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from jax import shard_map

__all__ = [
    "all_reduce", "all_gather", "reduce_scatter", "all_to_all", "ppermute",
    "ring_shift", "axis_index", "axis_size", "broadcast_from", "pmean",
    "run_sharded",
]


def all_reduce(x, axis_name: str, op: str = "sum"):
    """CommDevice::Reduce + Broadcast fused (comm.h:504) = one all-reduce."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown reduce op {op}")


def pmean(x, axis_name: str):
    return lax.pmean(x, axis_name)


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def ppermute(x, axis_name: str, perm):
    return lax.ppermute(x, axis_name, perm=perm)


def ring_shift(x, axis_name: str, shift: int = 1, *, size: Optional[int] = None):
    """Rotate shards around the ring — the primitive under ring attention
    and pipeline bubbles; rides neighbour ICI links."""
    if size is None:
        size = lax.psum(1, axis_name)
    perm = [(i, (i + shift) % size) for i in range(size)]
    return lax.ppermute(x, axis_name, perm=perm)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str):
    return lax.psum(1, axis_name)


def broadcast_from(x, axis_name: str, src: int = 0):
    """KVStore Broadcast analog: every member gets src's shard (masked
    all-reduce; XLA lowers this to a broadcast-shaped collective)."""
    is_src = lax.axis_index(axis_name) == src
    # select (not multiply): non-source shards may hold inf/NaN garbage and
    # 0*inf would poison the psum
    return lax.psum(jnp.where(is_src, x, jnp.zeros_like(x)), axis_name)


def run_sharded(fn: Callable, mesh: Mesh, in_specs, out_specs,
                check_vma: bool = False):
    """Wrap ``fn`` with shard_map over ``mesh`` — the escape hatch when XLA's
    automatic partitioning shouldn't own the schedule (ring attention,
    pipeline loops)."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=check_vma)
