#!/usr/bin/env python
"""Train a model zoo network and publish it to the local model store.

Fills the reference's pretrained-weights story
(python/mxnet/gluon/model_zoo/model_store.py) for air-gapped TPU
environments: instead of downloading from the Apache mirror, train a
checkpoint here (synthetic data or an MNIST/CIFAR-shaped npz you provide),
publish it sha1-keyed via ``model_store.publish_model_file``, and every
``get_model(name, pretrained=True)`` in this environment resolves it.

Examples:
    python tools/publish_pretrained.py --model resnet18_v1 --classes 10 \
        --steps 200 --img 32
    python tools/publish_pretrained.py --model mlp --data mnist.npz
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet18_v1")
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--img", type=int, default=32)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--data", default=None,
                    help="npz with arrays x (N,C,H,W) and y (N,); the "
                         "special value 'digits' uses sklearn's bundled "
                         "real handwritten-digit images (1797 samples, "
                         "held-out test split, measured accuracy); "
                         "synthetic blobs otherwise")
    ap.add_argument("--root", default=None,
                    help="model store root (default: the user cache dir)")
    ap.add_argument("--ship", action="store_true",
                    help="publish into the in-repo shipped store "
                         "(model_zoo/pretrained/ + MANIFEST.json) instead "
                         "of the user cache, recording measured accuracy")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import model_store, vision

    rng = onp.random.RandomState(args.seed)
    Xte = Yte = None
    if args.data == "digits":
        # REAL data shipped inside scikit-learn: 1797 8x8 handwritten
        # digits (a genuine UCI dataset, no network needed).  The
        # preprocessing + split is the shared single source of truth so
        # the recorded accuracy stays reproducible by the test suite.
        from mxnet_tpu.test_utils import load_digits_split

        X, Y, Xte, Yte = load_digits_split(img_size=args.img)
        args.classes = 10
        print(f"digits: {len(X)} train / {len(Xte)} test", file=sys.stderr)
    elif args.data:
        with onp.load(args.data) as z:
            X, Y = z["x"].astype(onp.float32), z["y"].astype(onp.int32)
    else:
        # separable synthetic blobs: per-class mean images + noise, enough
        # signal that the loss drop proves training happened
        means = rng.rand(args.classes, 3, args.img, args.img) * 2 - 1
        Y = rng.randint(0, args.classes, 2 * args.batch).astype(onp.int32)
        X = (means[Y] + 0.3 * rng.randn(len(Y), 3, args.img, args.img)
             ).astype(onp.float32)

    net = vision.get_model(args.model, classes=args.classes)
    net.initialize(mx.init.Xavier())
    net(nd.array(X[:1]))                       # deferred-shape probe
    net.hybridize()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": args.lr, "momentum": 0.9})
    ce = gloss.SoftmaxCrossEntropyLoss()
    n = len(X)
    t0 = time.time()
    first = last = None
    for step in range(args.steps):
        idx = rng.randint(0, n, args.batch)
        xb, yb = nd.array(X[idx]), nd.array(Y[idx])
        with autograd.record():
            out = net(xb)
            loss = ce(out, yb).mean()
        loss.backward()
        trainer.step(args.batch)
        v = float(loss.asscalar())
        first = v if first is None else first
        last = v
        if step % 20 == 0:
            print(f"step {step}: loss {v:.4f}", file=sys.stderr)
    print(f"trained {args.steps} steps in {time.time() - t0:.1f}s: "
          f"loss {first:.4f} -> {last:.4f}", file=sys.stderr)

    def _accuracy(Xa, Ya, bs=64):
        correct = 0
        for i in range(0, len(Xa), bs):
            out = net(nd.array(Xa[i:i + bs])).asnumpy()
            correct += int((out.argmax(axis=1) == Ya[i:i + bs]).sum())
        return correct / len(Xa)

    acc = {}
    if Xte is not None:
        acc = {"train_acc": round(_accuracy(X, Y), 4),
               "test_acc": round(_accuracy(Xte, Yte), 4)}
        print(f"accuracy: train {acc['train_acc']:.4f} "
              f"test {acc['test_acc']:.4f}", file=sys.stderr)

    with tempfile.TemporaryDirectory() as td:
        params_path = os.path.join(td, f"{args.model}.params")
        net.save_parameters(params_path)
        if args.ship:
            import hashlib
            import json
            import shutil

            shipped = os.path.join(os.path.dirname(model_store.__file__),
                                   "pretrained")
            os.makedirs(shipped, exist_ok=True)
            digest = hashlib.sha1(open(params_path, "rb").read()).hexdigest()
            fname = f"{args.model}-{digest[:8]}.params"
            dst = os.path.join(shipped, fname)
            shutil.copyfile(params_path, dst)
            mpath = os.path.join(shipped, "MANIFEST.json")
            manifest = (json.load(open(mpath)) if os.path.exists(mpath)
                        else {})
            prov = ("trained in-repo by tools/publish_pretrained.py on "
                    f"data={args.data or 'synthetic'} ({args.steps} steps, "
                    f"img {args.img}); accuracies measured on a fixed "
                    "held-out split" if acc else
                    "trained in-repo by tools/publish_pretrained.py on "
                    "synthetic class-mean blobs: architecture-correct demo "
                    "checkpoint; NOT real-data accuracy")
            manifest[args.model] = {"file": fname, "sha1": digest,
                                    "classes": args.classes,
                                    "provenance": prov, **acc}
            json.dump(manifest, open(mpath, "w"), indent=2)
            # drop superseded checkpoints for this model
            for f in os.listdir(shipped):
                if (f.startswith(args.model + "-") and f != fname
                        and f.endswith(".params")):
                    os.remove(os.path.join(shipped, f))
        else:
            dst = model_store.publish_model_file(params_path, args.model,
                                                 root=args.root)
    print(dst)
    return 0


if __name__ == "__main__":
    sys.exit(main())
