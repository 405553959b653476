"""Gluon losses (reference ``python/mxnet/gluon/loss.py``, 1,113 LoC).

All losses follow the reference contract: per-sample loss with optional
``sample_weight`` masking and batch-axis mean, returning shape
``(batch,)``-reduced-to-scalar-mean only at user level (the reference keeps
the batch axis; so do we).
"""
from __future__ import annotations

import numpy as onp

from ..ndarray import NDArray
from ..ndarray.ndarray import invoke, _as_nd
from .block import HybridBlock

__all__ = [
    "Loss", "L2Loss", "L1Loss",
    "SigmoidBinaryCrossEntropyLoss", "SigmoidBCELoss",
    "SoftmaxCrossEntropyLoss", "SoftmaxCELoss", "MultiTokenCrossEntropyLoss",
    "KLDivLoss", "CTCLoss", "HuberLoss", "HingeLoss", "SquaredHingeLoss",
    "LogisticLoss", "TripletLoss", "PoissonNLLLoss", "CosineEmbeddingLoss",
    "SDMLLoss",
]


def _apply_weighting(loss, weight=None, sample_weight=None):
    """Reference loss.py:49 _apply_weighting."""
    if sample_weight is not None:
        loss = loss * sample_weight
    if weight is not None:
        assert isinstance(weight, (int, float)), "weight must be numeric"
        loss = loss * weight
    return loss


def _reshape_like(pred, label):
    if pred.shape != label.shape:
        label = label.reshape(pred.shape)
    return label


class Loss(HybridBlock):
    """Base loss (reference loss.py:74)."""

    def __init__(self, weight, batch_axis):
        super().__init__()
        self._weight = weight
        self._batch_axis = batch_axis

    def __repr__(self):
        return f"{type(self).__name__}(batch_axis={self._batch_axis}, w={self._weight})"

    def _batch_mean(self, loss):
        axes = tuple(i for i in range(loss.ndim) if i != self._batch_axis)
        if not axes:
            return loss
        return loss.mean(axis=axes)


class L2Loss(Loss):
    def __init__(self, weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = (pred - label).square()
        loss = _apply_weighting(loss, self._weight / 2, sample_weight)
        return self._batch_mean(loss)


class L1Loss(Loss):
    def __init__(self, weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = (pred - label).abs()
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._batch_mean(loss)


class SigmoidBinaryCrossEntropyLoss(Loss):
    """Reference loss.py SigmoidBinaryCrossEntropyLoss (numerically-stable
    logits form)."""

    def __init__(self, from_sigmoid=False, weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._from_sigmoid = from_sigmoid

    def forward(self, pred, label, sample_weight=None, pos_weight=None):
        label = _reshape_like(pred, label)
        if not self._from_sigmoid:
            relu_p = invoke("relu", [pred], {})
            abs_p = pred.abs()
            softplus = invoke("Activation", [-abs_p], {"act_type": "softrelu"})
            if pos_weight is None:
                loss = relu_p - pred * label + softplus
            else:
                loss = relu_p - pred * label + softplus * (
                    (pos_weight - 1) * label + 1
                )
        else:
            eps = 1e-12
            if pos_weight is None:
                loss = -((pred + eps).log() * label
                         + (1.0 - pred + eps).log() * (1.0 - label))
            else:
                loss = -((pred + eps).log() * label * pos_weight
                         + (1.0 - pred + eps).log() * (1.0 - label))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._batch_mean(loss)


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class SoftmaxCrossEntropyLoss(Loss):
    """Reference loss.py SoftmaxCrossEntropyLoss."""

    def __init__(self, axis=-1, sparse_label=True, from_logits=False,
                 weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._axis = axis
        self._sparse_label = sparse_label
        self._from_logits = from_logits

    def forward(self, pred, label, sample_weight=None):
        if self._sparse_label and not self._from_logits:
            # picks the label's logit, then normalises that one number:
            # no log-softmax of pred's shape is formed (ops/nn.py)
            loss = invoke("sparse_softmax_cross_entropy", [pred, label],
                          {"axis": self._axis})
        else:
            if not self._from_logits:
                pred = invoke("log_softmax", [pred], {"axis": self._axis})
            if self._sparse_label:
                loss = -invoke("pick", [pred, label],
                               {"axis": self._axis, "keepdims": False})
            else:
                label = _reshape_like(pred, label)
                loss = -(pred * label).sum(axis=self._axis, keepdims=False)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._batch_mean(loss)


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class MultiTokenCrossEntropyLoss(Loss):
    """Next-token cross-entropy plus the weighted losses of a model's
    multi-token-prediction modules (op ``multi_token_cross_entropy``).
    ``pred`` (batch, depths, seq, classes) as such a model returns it,
    ``label`` (batch, seq) the next token at every position;
    ``depth_weights[d]`` weighs depth ``d`` (1 for the next token, the
    paper's lambda for the rest).  Returns (batch,)."""

    def __init__(self, depth_weights=(1.0, 0.3), weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._depth_weights = tuple(float(w) for w in depth_weights)

    def forward(self, pred, label, sample_weight=None):
        loss = invoke("multi_token_cross_entropy", [pred, label],
                      {"depth_weights": self._depth_weights})
        return _apply_weighting(loss, self._weight, sample_weight)


class KLDivLoss(Loss):
    def __init__(self, from_logits=True, axis=-1, weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._from_logits = from_logits
        self._axis = axis

    def forward(self, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = invoke("log_softmax", [pred], {"axis": self._axis})
        eps = 1e-12
        loss = label * ((label + eps).log() - pred)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._batch_mean(loss)


class CTCLoss(Loss):
    """Connectionist temporal classification (reference loss.py CTCLoss;
    op src/operator/nn/ctc_loss.cc → lax.scan forward algorithm)."""

    def __init__(self, layout="NTC", label_layout="NT", weight=None):
        assert layout in ("NTC", "TNC")
        assert label_layout in ("NT", "TN")
        self._layout = layout
        self._label_layout = label_layout
        super().__init__(weight, label_layout.find("N"))

    def forward(self, pred, label, pred_lengths=None, label_lengths=None,
                sample_weight=None):
        if self._layout == "NTC":
            pred = pred.transpose((1, 0, 2))
        if self._batch_axis == 1:
            label = label.transpose((1, 0))
        args = [pred, label]
        attrs = {"use_data_lengths": pred_lengths is not None,
                 "use_label_lengths": label_lengths is not None,
                 "blank_label": "last"}
        if pred_lengths is not None:
            args.append(pred_lengths)
        if label_lengths is not None:
            args.append(label_lengths)
        loss = invoke("CTCLoss", args, attrs)
        return _apply_weighting(loss, self._weight, sample_weight)


class HuberLoss(Loss):
    def __init__(self, rho=1, weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._rho = rho

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = (pred - label).abs()
        loss = invoke("where", [
            loss > self._rho,
            loss - 0.5 * self._rho,
            (0.5 / self._rho) * loss.square(),
        ], {})
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._batch_mean(loss)


class HingeLoss(Loss):
    def __init__(self, margin=1, weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = invoke("relu", [self._margin - pred * label], {})
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._batch_mean(loss)


class SquaredHingeLoss(Loss):
    def __init__(self, margin=1, weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = invoke("relu", [self._margin - pred * label], {}).square()
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._batch_mean(loss)


class LogisticLoss(Loss):
    def __init__(self, weight=1.0, batch_axis=0, label_format="signed"):
        super().__init__(weight, batch_axis)
        assert label_format in ("signed", "binary")
        self._label_format = label_format

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        if self._label_format == "signed":
            label = (label + 1.0) / 2.0
        loss = invoke("relu", [pred], {}) - pred * label + invoke(
            "Activation", [-pred.abs()], {"act_type": "softrelu"})
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._batch_mean(loss)


class TripletLoss(Loss):
    def __init__(self, margin=1, weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)
        self._margin = margin

    def forward(self, pred, positive, negative, sample_weight=None):
        positive = _reshape_like(pred, positive)
        negative = _reshape_like(pred, negative)
        loss = ((pred - positive).square() - (pred - negative).square()).sum(
            axis=tuple(range(1, pred.ndim))) + self._margin
        loss = invoke("relu", [loss], {})
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return loss


class PoissonNLLLoss(Loss):
    def __init__(self, weight=1.0, from_logits=True, batch_axis=0,
                 compute_full=False):
        super().__init__(weight, batch_axis)
        self._from_logits = from_logits
        self._compute_full = compute_full

    def forward(self, pred, label, sample_weight=None, epsilon=1e-08):
        label = _reshape_like(pred, label)
        if self._from_logits:
            loss = pred.exp() - label * pred
        else:
            loss = pred - label * (pred + epsilon).log()
        if self._compute_full:
            # Stirling approximation for log(label!)
            stirling = (label * label.log() - label
                        + 0.5 * (2 * onp.pi * label).log())
            loss = loss + invoke("where", [label > 1, stirling,
                                           label * 0], {})
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return loss.mean()


class CosineEmbeddingLoss(Loss):
    def __init__(self, weight=1.0, batch_axis=0, margin=0):
        super().__init__(weight, batch_axis)
        self._margin = margin

    def forward(self, input1, input2, label, sample_weight=None):
        input1 = _reshape_like(input1, input2)
        cos = (input1 * input2).sum(axis=-1) / (
            (input1.square().sum(axis=-1).sqrt()
             * input2.square().sum(axis=-1).sqrt()) + 1e-12
        )
        label = label.reshape(cos.shape)
        pos = 1.0 - cos
        neg = invoke("relu", [cos - self._margin], {})
        loss = invoke("where", [label == 1, pos, neg], {})
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return loss


class SDMLLoss(Loss):
    """Batchwise Smoothed Deep Metric Learning loss (reference
    loss.py:997, arXiv:1905.12786): every other row of the aligned batch
    acts as a negative; the softmax over negative distances is pulled
    toward a label-smoothed identity matrix with a KL objective."""

    def __init__(self, smoothing_parameter=0.3, weight=1.0, batch_axis=0):
        super().__init__(weight, batch_axis)
        self.kl_loss = KLDivLoss(from_logits=True)
        self.smoothing_parameter = smoothing_parameter

    def _compute_distances(self, x1, x2):
        # [B,1,D] - [1,B,D] -> pairwise squared euclidean [B,B]
        x1_ = x1.expand_dims(1)
        x2_ = x2.expand_dims(0)
        return ((x1_ - x2_) ** 2).sum(axis=2)

    def _compute_labels(self, batch_size, ctx):
        gold = invoke("eye", [], {"N": batch_size})
        s = self.smoothing_parameter
        return gold * (1 - s) + (1 - gold) * s / (batch_size - 1)

    def forward(self, x1, x2):
        batch_size = x1.shape[0]
        labels = self._compute_labels(batch_size, x1.ctx)
        distances = self._compute_distances(x1, x2)
        log_probs = invoke("log_softmax", [-distances], {"axis": 1})
        # kl_loss batch-means over rows; scale by batch_size to recover
        # the per-row KL sum (the reference multiplies the same way)
        return self.kl_loss(log_probs, labels) * batch_size
