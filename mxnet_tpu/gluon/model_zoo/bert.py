"""Gluon BERT encoder (user-API parity model).

The reference ships the fused transformer attention ops
(src/operator/contrib/transformer.cc:650-740 — interleaved_matmul_selfatt_qk/
valatt) and leaves the model to GluonNLP; BASELINE config 4 is "GluonNLP
BERT-base pretrain (transformer ops + LAMB)".  This module provides that
model natively: a HybridBlock BERT built on those same contrib ops, so
``net.hybridize()`` stages the whole encoder into one XLA program.

For pod-scale training use ``mxnet_tpu.models.transformer_lm`` (the
TPU-native scale recipe with tp/sp/ep/pp shardings); this class is the
Gluon-API surface (works with autograd/Trainer/ShardedTrainer directly).
"""
from __future__ import annotations

import math
from typing import Optional

from ... import autograd
from ... import random as _random
from ...ndarray.ndarray import _wrap, invoke
from ..block import HybridBlock
from ..nn import Dense, Dropout, Embedding, GELU, HybridSequential, LayerNorm

__all__ = ["BERTEncoderLayer", "BERTModel", "bert_base", "bert_small",
           "BERTMaskedLMHead"]


class BERTSelfAttention(HybridBlock):
    """Multi-head self-attention: the interleaved projection (reference
    transformer.cc layout), then scores, softmax, probability dropout and
    value product as ONE operator, ``interleaved_selfatt`` (the Pallas
    flash kernel on a TPU, ops/contrib.py)."""

    def __init__(self, units: int, num_heads: int, dropout: float = 0.0):
        super().__init__()
        assert units % num_heads == 0
        self._units = units
        self._num_heads = num_heads
        self._dropout = dropout
        self.qkv = Dense(3 * units, flatten=False, in_units=units)
        self.out_proj = Dense(units, flatten=False, in_units=units)

    def forward(self, x):
        # x: [batch, seq, units].  The projections work on the last axis,
        # so they take x as it lies; only the operator speaks the
        # interleaved [seq, batch, 3*units], and its kernels undo that
        # transpose themselves: the compiled program moves nothing.
        qkv = self.qkv(x).transpose((1, 0, 2))
        inputs = [qkv]
        training = bool(self._dropout) and autograd.is_training()
        if training:
            # the step's key, drawn as gluon.nn.Dropout draws it
            inputs.append(_wrap(_random.next_key(), qkv.ctx))
        out = invoke("interleaved_selfatt", inputs,
                     {"heads": self._num_heads, "p": self._dropout,
                      "training": training})
        return self.out_proj(out.transpose((1, 0, 2)))


class BERTEncoderLayer(HybridBlock):
    """Pre-LN transformer encoder layer."""

    def __init__(self, units: int, mlp_units: int, num_heads: int,
                 dropout: float = 0.0):
        super().__init__()
        self.ln1 = LayerNorm(in_channels=units)
        self.attn = BERTSelfAttention(units, num_heads, dropout)
        self.ln2 = LayerNorm(in_channels=units)
        self.ffn_1 = Dense(mlp_units, flatten=False, in_units=units)
        self.gelu = GELU()
        self.ffn_2 = Dense(units, flatten=False, in_units=mlp_units)
        self.dropout = Dropout(dropout) if dropout else None

    def forward(self, x):
        h = self.attn(self.ln1(x))
        if self.dropout is not None:
            h = self.dropout(h)
        x = x + h
        m = self.ffn_2(self.gelu(self.ffn_1(self.ln2(x))))
        if self.dropout is not None:
            m = self.dropout(m)
        return x + m


class BERTModel(HybridBlock):
    """BERT encoder: token+segment+position embeddings, N layers, final LN.

    forward(tokens[B,S], segments[B,S]) -> hidden [B, S, units].
    """

    def __init__(self, vocab_size: int = 30528, units: int = 768,
                 mlp_units: int = 3072, num_layers: int = 12,
                 num_heads: int = 12, max_len: int = 512,
                 num_segments: int = 2, dropout: float = 0.1):
        super().__init__()
        self._max_len = max_len
        self.word_embed = Embedding(vocab_size, units)
        self.segment_embed = Embedding(num_segments, units)
        self.pos_embed = Embedding(max_len, units)
        self.embed_ln = LayerNorm(in_channels=units)
        self.embed_dropout = Dropout(dropout) if dropout else None
        self.layers = HybridSequential()
        for _ in range(num_layers):
            self.layers.add(BERTEncoderLayer(units, mlp_units, num_heads,
                                             dropout))
        self.final_ln = LayerNorm(in_channels=units)

    def forward(self, tokens, segments=None):
        pos = invoke("arange_like", [tokens], {"axis": 1})
        x = self.word_embed(tokens) + self.pos_embed(pos)
        if segments is not None:
            x = x + self.segment_embed(segments)
        x = self.embed_ln(x)
        if self.embed_dropout is not None:
            x = self.embed_dropout(x)
        x = self.layers(x)
        return self.final_ln(x)


class BERTMaskedLMHead(HybridBlock):
    """MLM decoder head (tied projection left to the caller via in_units)."""

    def __init__(self, vocab_size: int, units: int = 768):
        super().__init__()
        self.transform = Dense(units, flatten=False, in_units=units)
        self.gelu = GELU()
        self.ln = LayerNorm(in_channels=units)
        self.decoder = Dense(vocab_size, flatten=False, in_units=units)

    def forward(self, hidden):
        return self.decoder(self.ln(self.gelu(self.transform(hidden))))


def bert_base(vocab_size: int = 30528, dropout: float = 0.1, **kwargs):
    return BERTModel(vocab_size=vocab_size, units=768, mlp_units=3072,
                     num_layers=12, num_heads=12, dropout=dropout, **kwargs)


def bert_small(vocab_size: int = 30528, dropout: float = 0.1, **kwargs):
    return BERTModel(vocab_size=vocab_size, units=256, mlp_units=1024,
                     num_layers=4, num_heads=4, dropout=dropout, **kwargs)
