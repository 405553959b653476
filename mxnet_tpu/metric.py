"""Evaluation metrics (reference ``python/mxnet/gluon/metric.py``, 1,856 LoC).

Accumulation is two-tier (the async pipeline engine, docs/PERF.md
"Pipelined train loop"):

- **device-side accumulators** (default, ``MXNET_METRIC_DEVICE=1``):
  when ``update()`` receives device NDArrays and the metric has a device
  kernel, the per-batch reduction runs as a compiled accumulate enqueued
  on the XLA stream — NO per-batch host sync.  The host read happens
  only at ``.get()`` / ``engine.waitall()`` or every
  ``MXNET_METRIC_SYNC_STEPS`` updates (which also bounds f32
  accumulation drift).
- **host accumulation** for metrics without a device kernel (confusion-
  matrix families, custom metrics) and under
  ``MXNET_ENGINE_TYPE=NaiveEngine`` — every device->host read on this
  path is counted LOUDLY in :func:`host_sync_count`, so a silent
  per-batch ``float()`` sync in the train loop is observable instead of
  a mystery stall.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence, Union

import numpy as onp

from .base import MXNetError
from .ndarray import NDArray

__all__ = [
    "EvalMetric", "CompositeEvalMetric", "Accuracy", "TopKAccuracy",
    "BinaryAccuracy", "F1", "Fbeta", "MCC", "Perplexity", "MAE", "MSE",
    "RMSE", "CrossEntropy", "NegativeLogLikelihood", "PearsonCorrelation",
    "PCC", "Loss", "CustomMetric", "MeanCosineSimilarity",
    "MeanPairwiseDistance", "np", "create", "check_label_shapes",
    "host_sync_count", "reset_host_sync_count",
]

# device->host reads performed by metric HOST paths (metrics bypassing
# the device-accumulator path, or the path disabled): the loud fallback
# counter — tests assert the device path leaves it at 0
from . import telemetry as _telemetry  # noqa: E402

_HOST_SYNC = _telemetry.counter(
    "metric.host_sync",
    "blocking per-update device->host reads by metrics that bypassed "
    "the device accumulator path (no kernel / disabled / NaiveEngine)")


def host_sync_count() -> int:
    """Blocking per-update device->host reads by metrics that bypassed
    the device accumulator path (no kernel / disabled / NaiveEngine).
    (View over the ``metric.host_sync`` registry counter.)"""
    return int(_HOST_SYNC.value)


def reset_host_sync_count() -> None:
    _HOST_SYNC.reset()

_REGISTRY: Dict[str, type] = {}


def _register(*names):
    def deco(cls):
        for n in names + (cls.__name__.lower(),):
            _REGISTRY[n.lower()] = cls
        return cls

    return deco


def create(metric, *args, **kwargs):
    """Create a metric by name/callable/list (reference metric.py create)."""
    if callable(metric):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, (list, tuple)):
        composite = CompositeEvalMetric()
        for m in metric:
            composite.add(create(m, *args, **kwargs))
        return composite
    if isinstance(metric, str):
        cls = _REGISTRY.get(metric.lower())
        if cls is None:
            raise ValueError(
                f"unknown metric '{metric}'; have {sorted(_REGISTRY)}")
        return cls(*args, **kwargs)
    raise TypeError(f"cannot create metric from {metric!r}")


def check_label_shapes(labels, preds, wrap=False, shape=False):
    if not shape:
        lshape, pshape = len(labels), len(preds)
    else:
        lshape, pshape = labels.shape, preds.shape
    if lshape != pshape:
        raise ValueError(
            f"Shape of labels {lshape} does not match shape of "
            f"predictions {pshape}")
    if wrap:
        if isinstance(labels, NDArray):
            labels = [labels]
        if isinstance(preds, NDArray):
            preds = [preds]
    return labels, preds


def _host(x) -> onp.ndarray:
    if isinstance(x, NDArray):
        # the loud fallback: every host-path sync on a device array is
        # counted, never silent (metric.host_sync_count)
        _HOST_SYNC.inc()
        return x.asnumpy()
    return onp.asarray(x)


class EvalMetric:
    """Base metric (reference metric.py EvalMetric)."""

    # lazily-built jax.jit of _device_batch; class attr so reset() never
    # drops the compiled kernel
    _dev_fn = None

    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = str(name)
        self.output_names = output_names
        self.label_names = label_names
        self._kwargs = kwargs
        self.reset()

    # -- device-side accumulation ---------------------------------------
    def _device_batch(self, label, pred):
        """Per-batch device kernel: (label, pred) jax arrays ->
        ``(sum, count)`` scalars, numerically mirroring the host
        ``update()``.  ``None`` (the base default) = host-only metric."""
        return None

    def _device_ok(self) -> bool:
        if type(self)._device_batch is EvalMetric._device_batch:
            return False
        from . import config as _config
        from . import engine as _engine

        return (not _engine.is_naive()
                and bool(_config.get("MXNET_METRIC_DEVICE")))

    def _try_device_update(self, labels, preds) -> bool:
        """Enqueue this batch's accumulate as compiled device work (no
        host sync).  False -> caller runs the host path (counted in
        :func:`host_sync_count`)."""
        if not self._device_ok():
            return False
        import jax

        try:
            labels, preds = check_label_shapes(labels, preds, wrap=True)
        except ValueError:
            return False          # host path raises the documented error
        pairs = []
        for label, pred in zip(labels, preds):
            if not (isinstance(label, NDArray) and isinstance(pred, NDArray)):
                return False
            if isinstance(label._data, jax.core.Tracer) or \
                    isinstance(pred._data, jax.core.Tracer):
                return False
            pairs.append((label._data, pred._data))
        try:
            if type(self)._dev_fn is None:
                type(self)._dev_fn = jax.jit(type(self)._device_batch,
                                             static_argnums=(0,))
            # compute every pair BEFORE touching the accumulator, so a
            # trace failure on any pair leaves state clean for the host
            # fallback (no half-applied batch)
            batch = [type(self)._dev_fn(self, l, p) for l, p in pairs]
        except Exception:
            return False
        # list append, NOT an eager device add: one compiled accumulate
        # per batch is the whole per-update cost (a tiny jnp add would
        # pay ~10x the kernel's dispatch overhead again)
        self._dev_pairs.extend(batch)
        self._dev_pending += 1
        from . import engine as _engine

        _engine.register_drainable(self)
        from . import config as _config

        if self._dev_pending >= _config.get("MXNET_METRIC_SYNC_STEPS"):
            self._fold_device()
        return True

    def _fold_device(self) -> None:
        """The host read: fold the pending device scalars into the host
        sums.  Happens at .get(), engine.waitall() (via drain), or every
        MXNET_METRIC_SYNC_STEPS updates — never per batch; by fold time
        the scalars have long materialized, so the reads don't stall."""
        pairs = getattr(self, "_dev_pairs", None)
        if not pairs:
            return
        self._dev_pairs = []
        self._dev_pending = 0
        for s, n in pairs:
            self.sum_metric += float(onp.asarray(s))
            self.num_inst += int(round(float(onp.asarray(n))))

    def drain(self) -> None:
        """engine.waitall() hook: land outstanding device accumulation."""
        self._fold_device()

    def __str__(self):
        return f"EvalMetric: {dict(zip(*self.get()))}"

    def get_config(self):
        config = dict(self._kwargs)
        config.update({"metric": type(self).__name__, "name": self.name,
                       "output_names": self.output_names,
                       "label_names": self.label_names})
        return config

    def update_dict(self, label: dict, pred: dict):
        if self.output_names is not None:
            pred = [pred[n] for n in self.output_names]
        else:
            pred = list(pred.values())
        if self.label_names is not None:
            label = [label[n] for n in self.label_names]
        else:
            label = list(label.values())
        self.update(label, pred)

    def update(self, labels, preds):
        raise NotImplementedError

    def reset(self):
        self.num_inst = 0
        self.sum_metric = 0.0
        # device accumulator state (see _try_device_update): reset drops
        # pending device scalars too — a cleared metric must not fold a
        # previous epoch's batches at the next get()
        self._dev_pairs = []
        self._dev_pending = 0

    def get(self):
        self._fold_device()
        if self.num_inst == 0:
            return self.name, float("nan")
        return self.name, self.sum_metric / self.num_inst

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))


class CompositeEvalMetric(EvalMetric):
    """Manage multiple metrics (reference CompositeEvalMetric)."""

    def __init__(self, metrics=None, name="composite", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names)
        self.metrics = [create(m) for m in (metrics or [])]

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        return self.metrics[index]

    def update(self, labels, preds):
        for m in self.metrics:
            m.update(labels, preds)

    def reset(self):
        for m in getattr(self, "metrics", []):
            m.reset()

    def get(self):
        names, values = [], []
        for m in self.metrics:
            n, v = m.get()
            names.extend(n if isinstance(n, list) else [n])
            values.extend(v if isinstance(v, list) else [v])
        return names, values


@_register("acc")
class Accuracy(EvalMetric):
    def __init__(self, axis=-1, name="accuracy", **kwargs):
        super().__init__(name, axis=axis, **kwargs)
        self.axis = axis

    def _device_batch(self, label, pred):
        import jax.numpy as jnp

        if pred.ndim > label.ndim:
            pred = pred.argmax(axis=self.axis)
        if pred.shape != label.shape:      # static under trace: the host
            raise ValueError("shape mismatch")   # path raises it properly
        pred = pred.astype(jnp.int32).ravel()
        label = label.astype(jnp.int32).ravel()
        return ((pred == label).sum().astype(jnp.float32),
                jnp.float32(label.shape[0]))

    def update(self, labels, preds):
        if self._try_device_update(labels, preds):
            return
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            pred = _host(pred)
            label = _host(label)
            if pred.ndim > label.ndim:
                pred = pred.argmax(axis=self.axis)
            pred = pred.astype(onp.int64).flatten()
            label = label.astype(onp.int64).flatten()
            check_label_shapes(label, pred, shape=True)
            self.sum_metric += float((pred == label).sum())
            self.num_inst += len(label)


@_register("top_k_accuracy", "top_k_acc")
class TopKAccuracy(EvalMetric):
    def __init__(self, top_k=1, name="top_k_accuracy", **kwargs):
        super().__init__(f"{name}_{top_k}", top_k=top_k, **kwargs)
        self.top_k = top_k
        assert top_k > 1, "use Accuracy for top_k=1"

    def _device_batch(self, label, pred):
        import jax.numpy as jnp

        topk = jnp.argsort(pred, axis=-1)[..., -self.top_k:]
        label = label.astype(jnp.int32)
        if topk.shape[:-1] != label.shape:
            raise ValueError("shape mismatch")
        hits = (topk == label[..., None]).sum().astype(jnp.float32)
        return hits, jnp.float32(label.size)

    def update(self, labels, preds):
        if self._try_device_update(labels, preds):
            return
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            pred = _host(pred)
            label = _host(label).astype(onp.int64)
            topk = onp.argsort(pred, axis=-1)[..., -self.top_k:]
            for j in range(self.top_k):
                self.sum_metric += float(
                    (topk[..., j].flatten() == label.flatten()).sum())
            self.num_inst += label.size


@_register("binary_accuracy")
class BinaryAccuracy(EvalMetric):
    def __init__(self, name="binary_accuracy", threshold=0.5, **kwargs):
        super().__init__(name, threshold=threshold, **kwargs)
        self.threshold = threshold

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            pred = (_host(pred).flatten() > self.threshold).astype(onp.int64)
            label = _host(label).flatten().astype(onp.int64)
            self.sum_metric += float((pred == label).sum())
            self.num_inst += len(label)


class _BinaryStats:
    def __init__(self):
        self.reset()

    def reset(self):
        self.tp = self.fp = self.tn = self.fn = 0

    def update(self, label, pred, threshold=0.5):
        if pred.ndim > 1 and pred.shape[-1] > 1:
            pred_label = pred.argmax(-1).flatten()
        else:
            pred_label = (pred.flatten() > threshold).astype(onp.int64)
        label = label.flatten().astype(onp.int64)
        self.tp += int(((pred_label == 1) & (label == 1)).sum())
        self.fp += int(((pred_label == 1) & (label == 0)).sum())
        self.tn += int(((pred_label == 0) & (label == 0)).sum())
        self.fn += int(((pred_label == 0) & (label == 1)).sum())

    @property
    def precision(self):
        d = self.tp + self.fp
        return self.tp / d if d else 0.0

    @property
    def recall(self):
        d = self.tp + self.fn
        return self.tp / d if d else 0.0

    def fbeta(self, beta):
        p, r = self.precision, self.recall
        if p + r == 0:
            return 0.0
        b2 = beta * beta
        return (1 + b2) * p * r / (b2 * p + r)

    @property
    def total(self):
        return self.tp + self.fp + self.tn + self.fn

    def matthewscc(self):
        terms = [(self.tp + self.fp), (self.tp + self.fn),
                 (self.tn + self.fp), (self.tn + self.fn)]
        denom = 1.0
        for t in terms:
            denom *= t if t else 1.0
        return (self.tp * self.tn - self.fp * self.fn) / math.sqrt(denom)


@_register("fbeta")
class Fbeta(EvalMetric):
    def __init__(self, name="fbeta", beta=1, average="macro", threshold=0.5,
                 **kwargs):
        super().__init__(name, **kwargs)
        self.beta = beta
        self.average = average
        self.threshold = threshold
        self._stats = _BinaryStats()

    def reset(self):
        super().reset()
        if hasattr(self, "_stats"):
            self._stats.reset()

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            self._stats.update(_host(label), _host(pred), self.threshold)

    def get(self):
        if self._stats.total == 0:
            return self.name, float("nan")
        return self.name, self._stats.fbeta(self.beta)


@_register("f1")
class F1(Fbeta):
    def __init__(self, name="f1", average="macro", threshold=0.5, **kwargs):
        super().__init__(name=name, beta=1, average=average,
                         threshold=threshold, **kwargs)


@_register("mcc")
class MCC(EvalMetric):
    def __init__(self, name="mcc", **kwargs):
        super().__init__(name, **kwargs)
        self._stats = _BinaryStats()

    def reset(self):
        super().reset()
        if hasattr(self, "_stats"):
            self._stats.reset()

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            self._stats.update(_host(label), _host(pred))

    def get(self):
        if self._stats.total == 0:
            return self.name, float("nan")
        return self.name, self._stats.matthewscc()


@_register("perplexity")
class Perplexity(EvalMetric):
    def __init__(self, ignore_label=None, axis=-1, name="perplexity",
                 **kwargs):
        super().__init__(name, ignore_label=ignore_label, **kwargs)
        self.ignore_label = ignore_label
        self.axis = axis

    def _device_batch(self, label, pred):
        import jax.numpy as jnp

        label = label.astype(jnp.int32).ravel()
        pred = pred.reshape(-1, pred.shape[-1])
        if pred.shape[0] != label.shape[0]:
            raise ValueError("shape mismatch")
        probs = pred[jnp.arange(label.shape[0]), label]
        nll = -jnp.log(jnp.maximum(probs, 1e-10))
        if self.ignore_label is not None:
            mask = (label != self.ignore_label).astype(jnp.float32)
            return (nll * mask).sum().astype(jnp.float32), mask.sum()
        return nll.sum().astype(jnp.float32), jnp.float32(label.shape[0])

    def update(self, labels, preds):
        if self._try_device_update(labels, preds):
            return
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            label = _host(label).astype(onp.int64).flatten()
            pred = _host(pred).reshape(-1, _host(pred).shape[-1])
            probs = pred[onp.arange(len(label)), label]
            if self.ignore_label is not None:
                mask = label != self.ignore_label
                probs = probs[mask]
            self.sum_metric += float(-onp.log(onp.maximum(probs, 1e-10)).sum())
            self.num_inst += len(probs)

    def get(self):
        self._fold_device()
        if self.num_inst == 0:
            return self.name, float("nan")
        return self.name, math.exp(self.sum_metric / self.num_inst)


def _align_regression(label, pred):
    """Expand a one-lower-rank label for broadcasting (reference MAE/MSE
    'if len(label.shape)==1 ... reshape' handling)."""
    if label.ndim == pred.ndim - 1:
        label = label[..., None]
    elif pred.ndim == label.ndim - 1:
        pred = pred[..., None]
    return label, pred


@_register("mae")
class MAE(EvalMetric):
    def __init__(self, name="mae", **kwargs):
        super().__init__(name, **kwargs)

    def _device_batch(self, label, pred):
        import jax.numpy as jnp

        label, pred = _align_regression(label, pred)
        return (jnp.abs(label - pred).mean().astype(jnp.float32)
                * label.shape[0], jnp.float32(label.shape[0]))

    def update(self, labels, preds):
        if self._try_device_update(labels, preds):
            return
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            label, pred = _align_regression(_host(label), _host(pred))
            self.sum_metric += float(
                onp.abs(label - pred).mean()) * label.shape[0]
            self.num_inst += label.shape[0]


@_register("mse")
class MSE(EvalMetric):
    def __init__(self, name="mse", **kwargs):
        super().__init__(name, **kwargs)

    def _device_batch(self, label, pred):
        import jax.numpy as jnp

        label, pred = _align_regression(label, pred)
        return (((label - pred) ** 2).mean().astype(jnp.float32)
                * label.shape[0], jnp.float32(label.shape[0]))

    def update(self, labels, preds):
        if self._try_device_update(labels, preds):
            return
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            label, pred = _align_regression(_host(label), _host(pred))
            self.sum_metric += float(
                ((label - pred) ** 2).mean()) * label.shape[0]
            self.num_inst += label.shape[0]


@_register("rmse")
class RMSE(MSE):
    def __init__(self, name="rmse", **kwargs):
        super().__init__(name=name, **kwargs)

    def get(self):
        self._fold_device()
        if self.num_inst == 0:
            return self.name, float("nan")
        return self.name, math.sqrt(self.sum_metric / self.num_inst)


@_register("ce", "cross-entropy")
class CrossEntropy(EvalMetric):
    def __init__(self, eps=1e-12, ignore_label=None, axis=-1,
                 name="cross-entropy", **kwargs):
        super().__init__(name, eps=eps, **kwargs)
        self.eps = eps
        self.ignore_label = ignore_label

    def _device_batch(self, label, pred):
        import jax.numpy as jnp

        label = label.astype(jnp.int32).ravel()
        pred = pred.reshape(-1, pred.shape[-1])
        if pred.shape[0] != label.shape[0]:
            raise ValueError("shape mismatch")
        probs = pred[jnp.arange(label.shape[0]), label]
        nll = -jnp.log(probs + self.eps)
        if self.ignore_label is not None:
            mask = (label != self.ignore_label).astype(jnp.float32)
            return (nll * mask).sum().astype(jnp.float32), mask.sum()
        return nll.sum().astype(jnp.float32), jnp.float32(label.shape[0])

    def update(self, labels, preds):
        if self._try_device_update(labels, preds):
            return
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            label = _host(label).astype(onp.int64).flatten()
            pred = _host(pred).reshape(-1, _host(pred).shape[-1])
            probs = pred[onp.arange(len(label)), label]
            if self.ignore_label is not None:
                mask = label != self.ignore_label
                probs = probs[mask]
            self.sum_metric += float(
                -onp.log(probs + self.eps).sum())
            self.num_inst += len(probs)


@_register("nll_loss")
class NegativeLogLikelihood(CrossEntropy):
    def __init__(self, eps=1e-12, name="nll-loss", **kwargs):
        super().__init__(eps=eps, name=name, **kwargs)


@_register("pearsonr")
class PearsonCorrelation(EvalMetric):
    def __init__(self, name="pearsonr", **kwargs):
        super().__init__(name, **kwargs)

    def reset(self):
        super().reset()
        self._x: List[onp.ndarray] = []
        self._y: List[onp.ndarray] = []

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            self._x.append(_host(label).flatten())
            self._y.append(_host(pred).flatten())
            self.num_inst += _host(label).size

    def get(self):
        if self.num_inst == 0:
            return self.name, float("nan")
        x = onp.concatenate(self._x)
        y = onp.concatenate(self._y)
        return self.name, float(onp.corrcoef(x, y)[0, 1])


@_register("pcc")
class PCC(EvalMetric):
    """Multiclass Pearson via confusion matrix (reference metric.py PCC)."""

    def __init__(self, name="pcc", **kwargs):
        super().__init__(name, **kwargs)

    def reset(self):
        super().reset()
        self._cm = onp.zeros((0, 0), onp.float64)

    def _grow(self, n):
        if n > self._cm.shape[0]:
            cm = onp.zeros((n, n), onp.float64)
            cm[:self._cm.shape[0], :self._cm.shape[1]] = self._cm
            self._cm = cm

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            label = _host(label).astype(onp.int64).flatten()
            pred = _host(pred)
            if pred.ndim > 1:
                pred = pred.argmax(-1)
            pred = pred.astype(onp.int64).flatten()
            n = int(max(label.max(), pred.max())) + 1
            self._grow(n)
            for lt, pt in zip(label, pred):
                self._cm[pt, lt] += 1
            self.num_inst += len(label)

    def get(self):
        if self.num_inst == 0:
            return self.name, float("nan")
        c = self._cm
        n = c.sum()
        x = c.sum(axis=1)  # predicted counts
        y = c.sum(axis=0)  # true counts
        cov_xy = (c.trace() * n - x @ y)
        cov_xx = (n * n - x @ x)
        cov_yy = (n * n - y @ y)
        denom = math.sqrt(cov_xx * cov_yy)
        return self.name, float(cov_xy / denom) if denom else 0.0


@_register("loss")
class Loss(EvalMetric):
    """Running mean of a loss output (reference metric.py Loss)."""

    def __init__(self, name="loss", **kwargs):
        super().__init__(name, **kwargs)

    def _device_batch(self, label, pred):
        import jax.numpy as jnp

        return pred.sum().astype(jnp.float32), jnp.float32(pred.size)

    def update(self, _, preds):
        if isinstance(preds, NDArray):
            preds = [preds]
        # label-free metric: the device path pairs each pred with itself
        # (the kernel ignores the label slot)
        if isinstance(preds, (list, tuple)) and \
                self._try_device_update(list(preds), list(preds)):
            return
        for pred in preds:
            loss = _host(pred)
            self.sum_metric += float(loss.sum())
            self.num_inst += loss.size


@_register("cos_sim")
class MeanCosineSimilarity(EvalMetric):
    def __init__(self, name="cos_sim", eps=1e-8, **kwargs):
        super().__init__(name, eps=eps, **kwargs)
        self.eps = eps

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            label, pred = _host(label), _host(pred)
            num = (label * pred).sum(-1)
            den = onp.linalg.norm(label, axis=-1) * \
                onp.linalg.norm(pred, axis=-1)
            sim = num / onp.maximum(den, self.eps)
            self.sum_metric += float(sim.sum())
            self.num_inst += sim.size


@_register("pdist")
class MeanPairwiseDistance(EvalMetric):
    def __init__(self, name="pdist", p=2, **kwargs):
        super().__init__(name, p=p, **kwargs)
        self.p = p

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            label, pred = _host(label), _host(pred)
            d = onp.linalg.norm((label - pred).reshape(label.shape[0], -1),
                                ord=self.p, axis=-1)
            self.sum_metric += float(d.sum())
            self.num_inst += d.size


class CustomMetric(EvalMetric):
    """Wrap fn(label, pred) -> float (reference CustomMetric)."""

    def __init__(self, feval, name=None, allow_extra_outputs=False, **kwargs):
        name = name or getattr(feval, "__name__", "custom")
        super().__init__(f"custom({name})", **kwargs)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            labels, preds = check_label_shapes(labels, preds, wrap=True)
        for label, pred in zip(labels, preds):
            v = self._feval(_host(label), _host(pred))
            if isinstance(v, tuple):
                s, n = v
                self.sum_metric += s
                self.num_inst += n
            else:
                self.sum_metric += v
                self.num_inst += 1


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """Wrap a numpy eval fn as a metric factory (reference metric.np)."""

    def feval(label, pred):
        return numpy_feval(label, pred)

    feval.__name__ = name or getattr(numpy_feval, "__name__", "custom")
    return CustomMetric(feval, name, allow_extra_outputs)
