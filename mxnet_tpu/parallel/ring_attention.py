"""Ring attention: sequence/context parallelism for long sequences.

The reference has NO long-context machinery (SURVEY.md §5: "no ring
attention, context/sequence parallelism ... anywhere" — its closest artifact
is the fused self-attention matmuls in src/operator/contrib/transformer.cc).
This module is the TPU-native replacement that makes sequence length a mesh
axis: Q/K/V are sharded over ``sp``; each step every device computes
attention of its local Q block against the K/V block currently resident,
then rotates K/V one hop around the ring (``ppermute`` on neighbour ICI
links), overlapping the next block's compute with the transfer.  Softmax is
accumulated online (flash-attention style running max / running sum), so the
full S×S score matrix never materializes.

Numerically identical to full softmax(QK^T/sqrt(d))V — verified in
tests/test_parallel.py against the dense reference on an 8-device CPU mesh.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

__all__ = ["ring_attention", "ring_attention_sharded", "local_attention_block"]


def local_attention_block(q, k, v, m_prev, l_prev, o_prev, *, scale,
                          mask=None):
    """One online-softmax accumulation step.

    q: [B, H, Sq, D]; k/v: [B, H, Sk, D];
    m_prev/l_prev: [B, H, Sq] running max / normalizer; o_prev: un-normalized
    output accumulator [B, H, Sq, D].
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard: fully-masked rows keep m_new finite enough for exp
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    o_new = o_prev * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, o_new


def ring_attention(q, k, v, axis_name: str = "sp", *, causal: bool = False,
                   scale: Optional[float] = None):
    """Blockwise ring attention over mesh axis ``axis_name``.

    Call INSIDE shard_map/pjit with q,k,v local shards [B, H, S_local, D].
    Sequence is laid out contiguously across the ring: device i holds tokens
    [i*S_local, (i+1)*S_local).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    Sk = k.shape[2]

    m0 = jnp.full((B, H, S), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((B, H, S), dtype=jnp.float32)
    o0 = jnp.zeros((B, H, S, D), dtype=jnp.float32)
    qf = q.astype(jnp.float32)

    def step(carry, t):
        m, l, o, kt, vt = carry
        # block kt/vt originated on device (my_idx + t) % n
        src = (my_idx + t) % n
        if causal:
            q_pos = my_idx * S + jnp.arange(S)
            k_pos = src * Sk + jnp.arange(Sk)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = jnp.broadcast_to(mask[None, None], (B, H, S, Sk))
        else:
            mask = None
        m, l, o = local_attention_block(
            qf, kt.astype(jnp.float32), vt.astype(jnp.float32), m, l, o,
            scale=scale, mask=mask)
        # rotate k/v to the next device; overlap with next iteration's compute
        perm = [(i, (i - 1) % n) for i in range(n)]
        kt = lax.ppermute(kt, axis_name, perm=perm)
        vt = lax.ppermute(vt, axis_name, perm=perm)
        return (m, l, o, kt, vt), None

    (m, l, o, _, _), _ = lax.scan(step, (m0, l0, o0, k, v), jnp.arange(n))
    # fully-masked rows (causal, leading tokens on later devices) have l=0
    l = jnp.where(l == 0, 1.0, l)
    out = o / l[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, *, axis_name: str = "sp",
                           causal: bool = False,
                           batch_axes=("dp",)):
    """Top-level entry: q,k,v are global arrays [B, H, S, D]; shards them
    over (batch_axes, sp) and runs the ring under shard_map."""
    spec = P(tuple(a for a in batch_axes if a in mesh.shape) or None, None,
             axis_name if axis_name in mesh.shape else None, None)
    fn = shard_map(
        partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
