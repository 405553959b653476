"""Operations from shapes: multiply-accumulates of convolutions and matrix
products.  The yardstick's arithmetic; each configuration's
``ops_per_sample`` adds these up for its own layer table.

A training step is counted as forward plus backward, backward at twice the
forward (one product for the input's gradient, one for the weight's), two
operations per multiply-accumulate, recomputation not counted.
"""
from __future__ import annotations

OPS_PER_MAC = 2
TRAIN_PASSES = 3          # forward + 2x forward for the backward pass


def conv_out(size: int, kernel: int, stride: int, pad: int) -> int:
    """Output extent of a convolution or pooling window ('valid' rounding)."""
    return (size + 2 * pad - kernel) // stride + 1


def conv2d_macs(h_out: int, w_out: int, c_in: int, c_out: int,
                kh: int, kw: int) -> int:
    """One image through one dense 2-D convolution."""
    return h_out * w_out * c_out * c_in * kh * kw


def dense_macs(rows: int, n_in: int, n_out: int) -> int:
    """``rows`` vectors through one ``n_in x n_out`` matrix product."""
    return rows * n_in * n_out


def attention_macs(heads: int, seq_q: int, seq_k: int, head_dim: int) -> int:
    """Score (QK^T) and value (PV) products of one sequence, unmasked."""
    return 2 * heads * seq_q * seq_k * head_dim


def train_ops(forward_macs: int) -> int:
    return OPS_PER_MAC * TRAIN_PASSES * forward_macs
