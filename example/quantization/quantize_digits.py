"""INT8 post-training quantization, end to end (reference
example/quantization/imagenet_gen_qsym_onednn.py workflow, TPU-native).

Loads the shipped REAL-data pretrained mobilenet (92.8% test accuracy on
scikit-learn's bundled handwritten digits), calibrates on a handful of
batches, converts to an int8 graph (conv+BN+relu folded, requantize
fused), and reports int8-vs-fp32 top-1 agreement and accuracy on the
held-out split.

Quantized convolutions run ``lax.conv`` s8 -> s32 on every backend.

    python example/quantization/quantize_digits.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.contrib import quantization as q
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.test_utils import load_digits_split


def main():
    net = vision.get_model("mobilenet0.25", pretrained=True)
    net.hybridize()
    Xtr, _, Xte, Yte = load_digits_split()

    # calibrate on TRAIN data — the scored split stays held out
    calib = [nd.array(Xtr[i:i + 32]) for i in range(0, 96, 32)]
    qnet = q.quantize_net(net, calib, calib_mode="naive")

    agree = correct_fp = correct_q = 0
    for i in range(0, len(Xte), 64):
        x = nd.array(Xte[i:i + 64])
        y = Yte[i:i + 64]
        ref = net(x).asnumpy().argmax(1)
        got = onp.asarray(qnet(x)).argmax(1)
        agree += int((ref == got).sum())
        correct_fp += int((ref == y).sum())
        correct_q += int((got == y).sum())
    n = len(Xte)
    print(f"fp32 accuracy:  {correct_fp / n:.4f}")
    print(f"int8 accuracy:  {correct_q / n:.4f}")
    print(f"top-1 agreement: {agree / n:.4f}")
    assert agree / n >= 0.97, "int8 predictions diverged from fp32"
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
