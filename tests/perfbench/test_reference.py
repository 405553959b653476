"""Each configuration's plain reference against the Gluon forward at a small
size on the CPU, and the operation counts against hand counts."""
import numpy as np
import pytest

import mxnet_tpu as mx
from perfbench import manifest, opcount

DRIVER = manifest.load_module("drivers", "train_fixed_shape")


def _forward_pair(cell, sizes, drop=None):
    """(Gluon logits, reference logits, scale) in float32 (no AMP), with the
    reference check's own weights, so that every bias, scale and shift is a
    term that shows.  ``drop`` zeroes the reference's parameters whose name
    ends in it: a term left out of the mathematics."""
    import jax

    c = manifest.resolve(cell, rehearse=True)
    cfg, sizes = c.config_module, {**c.sizes, **sizes}
    built = cfg.build(mx, sizes)
    net = built["net"]
    x, y = cfg.check_batch(5, sizes, c.mix)
    mode = mx.autograd.train_mode if sizes["check"]["mode"] == "train" \
        else mx.autograd.predict_mode
    with mode():
        net(mx.nd.array(x))                      # deferred shapes
    DRIVER._check_weights(net, sizes["check"], 5)
    with mode():
        got = net(mx.nd.array(x)).asnumpy()
    params = {n: p.data().asnumpy() for n, p in net.collect_params().items()}
    if drop:
        params = {n: v * (0 if n.endswith(drop) else 1)
                  for n, v in params.items()}
    with jax.default_matmul_precision("highest"):
        _, ref = cfg.reference(params, x, y, sizes)
    ref = np.asarray(ref)
    return got, ref, float(np.abs(ref).max())


@pytest.mark.parametrize("cell, sizes, tol", [
    ("resnet50_train", {}, 2e-3),                # full depth at 32 pixels
    ("bert_base_train_s512", {"num_hidden_layers": 2}, 2e-4),
])
def test_reference_equals_the_gluon_forward(cell, sizes, tol):
    got, ref, scale = _forward_pair(cell, sizes)
    assert got.shape == ref.shape
    assert np.abs(got - ref).max() / scale < tol


@pytest.mark.parametrize("cell, sizes, drop", [
    # (a convolution's bias in front of a batch norm cancels in the mean,
    # so it is no term of the function; these are)
    ("resnet50_train", {}, "body.4.beta"),
    ("resnet50_train", {}, "output.bias"),
    ("bert_base_train_s512", {"num_hidden_layers": 2}, "ffn_2.bias"),
    ("bert_base_train_s512", {"num_hidden_layers": 2}, "ln2.beta"),
])
def test_a_missing_term_fails_the_configurations_tolerance(cell, sizes, drop):
    got, ref, scale = _forward_pair(cell, sizes, drop=drop)
    tol = manifest.resolve(cell).sizes["check"]["logits_tol"]
    assert np.abs(got - ref).max() / scale > tol


def test_one_convolution_by_hand():
    # ResNet-50 stage 1, the block's last 1x1: 56x56 positions, 64 -> 256
    assert opcount.conv2d_macs(56, 56, 64, 256, 1, 1) == 51_380_224
    assert opcount.conv_out(224, 7, 2, 3) == 112
    assert opcount.conv_out(112, 3, 2, 1) == 56
    assert opcount.train_ops(51_380_224) == 6 * 51_380_224


def test_one_encoder_layer_by_hand():
    bert = manifest.resolve("bert_base_train_s128")
    # 128 tokens through qkv (768x2304), out (768x768), ffn (2 x 768x3072):
    # 128 x 7,077,888; scores and values: 2 x 12 heads x 128 x 128 x 64
    want = 128 * (1_769_472 + 589_824 + 2 * 2_359_296) + 25_165_824
    assert want == 931_135_488
    assert bert.config_module.layer_macs(bert.sizes, 128) == want
    # the whole model at 512: 12 layers, the head's transform, the decoder
    # over every position
    layer512 = 512 * 7_077_888 + 2 * 12 * 512 * 512 * 64
    assert bert.config_module.forward_macs(bert.sizes, 512) == \
        12 * layer512 + 512 * 768 * 768 + 512 * 768 * 30528
    s512 = manifest.resolve("bert_base_train_s512")
    per_sequence = s512.config_module.ops_per_sample(s512.sizes, s512.mix)
    assert per_sequence == 6 * (12 * layer512 + 512 * 768 * (768 + 30528))
    # 710 MFLOP a trained token; 11.64 TFLOP a step of 32 sequences
    assert round(per_sequence / 512 / 1e6) == 710
    assert s512.mix["batch_per_chip"] == 32
    assert round(32 * per_sequence / 1e12, 2) == 11.64


def test_the_whole_resnet50_by_hand_and_the_4_1_g_question():
    """The literature's "4.1 G" for ResNet-50 at 224 are multiply-accumulates
    (of the v1.5 variant), so a forward pass is 8.2 GFLOP of it;
    ``bench.py``'s ``3 * 4.1e9`` "FLOPs" a trained image is half the
    arithmetic.  The model zoo's v1 (stride on the 1x1) has 3.86 G."""
    rn = manifest.resolve("resnet50_train")
    stem = 112 * 112 * 3 * 64 * 49
    first = {1: 12_845_056 + 115_605_504 + 51_380_224 + 51_380_224}
    for stage in (2, 3, 4):     # 1x1 strided, 3x3, 1x1, projection
        first[stage] = 25_690_112 + 115_605_504 + 51_380_224 + 102_760_448
    rest = 51_380_224 + 115_605_504 + 51_380_224     # every later block
    blocks = {1: 3, 2: 4, 3: 6, 4: 3}
    v1 = stem + sum(first[s] + (blocks[s] - 1) * rest for s in blocks) \
        + 2048 * 1000
    assert v1 == 3_857_973_248
    assert rn.config_module.forward_macs(rn.sizes) == v1
    # v1.5 runs the first 1x1 of stages 2-4 before the stride: 4x its work
    v15 = v1 + 3 * (102_760_448 - 25_690_112)
    assert v15 == 4_089_184_256 and round(v15 / 1e9, 1) == 4.1
    assert rn.config_module.ops_per_sample(rn.sizes, rn.mix) == 6 * v1
