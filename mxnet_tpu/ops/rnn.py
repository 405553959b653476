"""Fused multi-layer RNN/LSTM/GRU operator.

Reference analog: the stateful fused RNN op (``src/operator/rnn-inl.h``
1,608 LoC + ``rnn.cc:451`` — vanilla CPU impl and cuDNN wrapper).
TPU-native design (SURVEY.md §2.2 "rnn*": *implement as XLA scan lowering*):
one ``lax.scan`` per layer-direction over time-major data; XLA pipelines the
per-step matmuls onto the MXU and fuses the gate math.  Gate layouts match
cuDNN (LSTM: i f g o; GRU: r z n) so exported weights are interchangeable
with the reference's packed format.

Weights arrive as separate arrays per (layer, direction): no cuDNN packed
1-D parameter blob — packing was a cuDNN calling-convention artifact, not a
feature; :mod:`mxnet_tpu.gluon.rnn` keeps per-layer named Parameters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .random import keep_mask
from .registry import register

__all__ = ["rnn_fused"]


def _step_rnn_tanh(x_proj, h, w_hh, b_hh):
    return jnp.tanh(x_proj + h @ w_hh.T + b_hh)


def _step_rnn_relu(x_proj, h, w_hh, b_hh):
    return jax.nn.relu(x_proj + h @ w_hh.T + b_hh)


def _layer_scan(mode, x, h0, c0, w_ih, w_hh, b_ih, b_hh, reverse=False):
    """Run one direction of one layer over time. x: (T, B, I)."""
    # hoist the input projection out of the scan: one big MXU matmul over
    # (T*B, I) instead of T small ones
    T, B, _ = x.shape
    x_proj = (x.reshape(T * B, -1) @ w_ih.T + b_ih).reshape(T, B, -1)
    if reverse:
        x_proj = jnp.flip(x_proj, axis=0)

    if mode == "lstm":
        def step(carry, xp):
            h, c = carry
            gates = xp + h @ w_hh.T + b_hh
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        (hT, cT), ys = lax.scan(step, (h0, c0), x_proj)
    elif mode == "gru":
        def step(h, xp):
            xr, xz, xn = jnp.split(xp, 3, axis=-1)
            hr, hz, hn = jnp.split(h @ w_hh.T + b_hh, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h = (1.0 - z) * n + z * h
            return h, h

        hT, ys = lax.scan(step, h0, x_proj)
        cT = None
    else:
        fn = _step_rnn_tanh if mode == "rnn_tanh" else _step_rnn_relu

        def step(h, xp):
            h = fn(xp, h, w_hh, b_hh)
            return h, h

        hT, ys = lax.scan(step, h0, x_proj)
        cT = None
    if reverse:
        ys = jnp.flip(ys, axis=0)
    return ys, hT, cT


@register("_rnn_fused", num_inputs=-1, num_outputs=-1)
def rnn_fused(arrays, mode="lstm", hidden_size=0, num_layers=1,
              bidirectional=False, dropout=0.0, has_cell_state=None):
    """arrays = [data(T,B,I), h0(L*D,B,H), (c0 if lstm),
    then per (layer, direction): w_ih, w_hh, b_ih, b_hh,
    (dropout PRNG key last, iff dropout > 0 — explicit so the op stays pure
    under whole-graph jit, same contract as ops/nn.py Dropout)].

    Returns (output(T,B,H*D), hT(L*D,B,H)[, cT]) — the fused op contract of
    the reference RNN op (rnn-inl.h state_outputs=True shape semantics).
    """
    ndir = 2 if bidirectional else 1
    is_lstm = mode == "lstm" if has_cell_state is None else has_cell_state
    data = arrays[0]
    h0 = arrays[1]
    idx = 2
    c0 = None
    if is_lstm:
        c0 = arrays[2]
        idx = 3
    weights = list(arrays[idx:])
    key = None
    if dropout > 0.0:
        key = weights.pop()
    assert len(weights) == 4 * num_layers * ndir, (
        f"expected {4 * num_layers * ndir} weight arrays, got {len(weights)}")

    x = data
    h_outs, c_outs = [], []
    for layer in range(num_layers):
        ys_dirs = []
        for d in range(ndir):
            wi = layer * ndir + d
            w_ih, w_hh, b_ih, b_hh = weights[4 * wi:4 * wi + 4]
            ys, hT, cT = _layer_scan(
                mode, x, h0[wi], c0[wi] if c0 is not None else None,
                w_ih, w_hh, b_ih, b_hh, reverse=(d == 1))
            ys_dirs.append(ys)
            h_outs.append(hT)
            if cT is not None:
                c_outs.append(cT)
        x = ys_dirs[0] if ndir == 1 else jnp.concatenate(ys_dirs, axis=-1)
        if dropout > 0.0 and layer < num_layers - 1:
            layer_key = jax.random.fold_in(key, layer)
            keep = keep_mask(layer_key, x.shape, 1.0 - dropout)
            x = jnp.where(keep, x / (1.0 - dropout), 0.0)

    hT = jnp.stack(h_outs)
    if is_lstm:
        return x, hT, jnp.stack(c_outs)
    return x, hT
