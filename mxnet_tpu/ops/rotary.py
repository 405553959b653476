"""Rotary position embedding (Su et al. 2021, arXiv:2104.09864).

``rope`` turns each position's vector by an angle that grows with the
position: feature ``i`` of the first half pairs with feature ``i`` of the
second half (the "rotate-half" pairing of the public GPT-NeoX and Hugging
Face code; the interleaved pairing of the paper is the same operator on
permuted weight columns), and the pair at position ``t`` is turned by
``t * theta^(-2i / dim)``.  Angles, sines and cosines are float32 whatever
arrives, and the result takes the input's type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

__all__ = ["rope"]

# the scope the device trace reads (perfbench/scope_view: a component that
# starts with a capital letter is a block scope)
ROPE_SCOPE = "Rotary"


@register("rope")
def rope(data, theta=10000.0):
    """``data`` (batch, seq, ..., dim), ``dim`` even: axis 1 holds positions
    0, 1, ...  Any axes between the sequence and the features (heads) are
    turned alike."""
    dim = data.shape[-1]
    if data.ndim < 3 or dim % 2:
        raise ValueError(f"rope: (batch, seq, ..., even dim), got "
                         f"{data.shape}")
    half = dim // 2
    with jax.named_scope(ROPE_SCOPE):
        inv_freq = float(theta) ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        angle = jnp.arange(data.shape[1], dtype=jnp.float32)[:, None] \
            * inv_freq                                       # (seq, half)
        shape = (1, data.shape[1]) + (1,) * (data.ndim - 3) + (half,)
        cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
        x = data.astype(jnp.float32)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1).astype(data.dtype)
