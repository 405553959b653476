"""End-to-end convergence test — mirrors the reference's
``tests/python/train/test_autograd.py``: MNISTIter over idx-format files,
multi-context train loop with ``gluon.utils.split_and_load``, accuracy
scoring, and a save/load resume check."""
import os
import struct

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon


def _write_idx_images(path, arr):
    """Pack uint8 images in MNIST idx3 format."""
    arr = arr.astype(onp.uint8)
    with open(path, "wb") as f:
        f.write(struct.pack(">IIII", 0x00000803, *arr.shape))
        f.write(arr.tobytes())


def _write_idx_labels(path, arr):
    with open(path, "wb") as f:
        f.write(struct.pack(">II", 0x00000801, arr.shape[0]))
        f.write(arr.astype(onp.uint8).tobytes())


@pytest.fixture(scope="module")
def mnist_files(tmp_path_factory):
    """Synthetic separable digits in REAL idx files (exercises the
    iter_mnist.cc-analog reader)."""
    root = tmp_path_factory.mktemp("mnist")
    rng = onp.random.RandomState(0)

    def make(n, seed):
        r = onp.random.RandomState(seed)
        y = r.randint(0, 10, size=n)
        x = r.uniform(0, 30, size=(n, 28, 28))
        for i, k in enumerate(y):
            rr, cc = divmod(int(k), 4)
            x[i, 7 * rr:7 * rr + 7, 7 * cc:7 * cc + 7] += 200
        return x, y

    xtr, ytr = make(1200, 1)
    xte, yte = make(400, 2)
    paths = {k: str(root / k) for k in
             ("train-img", "train-lbl", "val-img", "val-lbl")}
    _write_idx_images(paths["train-img"], xtr)
    _write_idx_labels(paths["train-lbl"], ytr)
    _write_idx_images(paths["val-img"], xte)
    _write_idx_labels(paths["val-lbl"], yte)
    return paths


def _get_net():
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(64, activation="relu"),
            gluon.nn.Dense(32, activation="relu"),
            gluon.nn.Dense(10))
    return net


def _score(net, val_data, ctx_list):
    metric = mx.metric.Accuracy()
    val_data.reset()
    for batch in val_data:
        datas = gluon.utils.split_and_load(batch.data[0], ctx_list)
        labels = gluon.utils.split_and_load(batch.label[0], ctx_list)
        metric.update(labels, [net(x) for x in datas])
    return metric.get()[1]


def test_train_autograd_end_to_end(mnist_files, tmp_path):
    train_data = mx.io.MNISTIter(image=mnist_files["train-img"],
                                 label=mnist_files["train-lbl"],
                                 data_shape=(784,), batch_size=100,
                                 shuffle=True, flat=True, seed=10)
    val_data = mx.io.MNISTIter(image=mnist_files["val-img"],
                               label=mnist_files["val-lbl"],
                               data_shape=(784,), batch_size=100,
                               shuffle=False, flat=True)
    ctx_list = [mx.cpu(0), mx.cpu(0)]

    net = _get_net()
    net.initialize(mx.init.Xavier(magnitude=2.24))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    for _epoch in range(3):
        train_data.reset()
        for batch in train_data:
            datas = gluon.utils.split_and_load(batch.data[0], ctx_list)
            labels = gluon.utils.split_and_load(batch.label[0], ctx_list)
            with autograd.record():
                losses = [loss_fn(net(x), y)
                          for x, y in zip(datas, labels)]
            for loss in losses:
                loss.backward()
            trainer.step(batch.data[0].shape[0])

    acc = _score(net, val_data, ctx_list)
    assert acc > 0.90, f"end-to-end training failed to converge: {acc}"

    # save -> fresh net -> load -> identical score (resume contract)
    path = str(tmp_path / "e2e.params")
    net.save_parameters(path)
    net2 = _get_net()
    net2.initialize()
    net2(mx.nd.zeros((1, 784)))          # materialize shapes
    net2.load_parameters(path)
    assert abs(_score(net2, val_data, ctx_list) - acc) < 1e-6
