"""ResNet-50 v1 (He et al. 2015, arXiv:1512.03385) as the model zoo builds it.

What is code in this configuration: how the net is built through the
program's public API, operations per image from the layer table in
``resnet50_v1.json``, seeded learnable images, and the plain reference
(``jax.numpy``, float32, no Gluon) the program's forward pass is held to.

The model-zoo bottleneck is the original v1: the stride of a stage's first
block sits on its FIRST 1x1 convolution (torchvision's "v1.5" moved it to
the 3x3), and the two 1x1 convolutions of a block carry a bias.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench import opcount

BN_EPS = 1e-5


# -- the system under test --------------------------------------------------
def build(mx, sizes):
    net = mx.gluon.model_zoo.vision.resnet50_v1(
        classes=sizes["classes"], layout=sizes["layout"],
        input_layout=sizes["layout"])
    net.initialize(mx.init.Xavier())
    # the reference check makes the net's first call, which resolves the
    # deferred shapes; no separate net(zeros) probe as in chip_smoke
    net.hybridize()
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    return {"net": net, "head_loss": lambda logits, y: ce(logits, y).mean(),
            "optimizer": sizes["optimizer"],
            "optimizer_params": dict(sizes["optimizer_params"])}


# -- operations from shapes -------------------------------------------------
def forward_macs(sizes) -> int:
    """Multiply-accumulates of one image's forward pass, every convolution
    and the classifier, from the layer table."""
    h = opcount.conv_out(sizes["image_size"], 7, 2, 3)
    c_in = sizes["stem_channels"]
    macs = opcount.conv2d_macs(h, h, 3, c_in, 7, 7)
    h = opcount.conv_out(h, 3, 2, 1)                      # max pool
    for stage, (blocks, c) in enumerate(zip(sizes["stage_blocks"],
                                            sizes["stage_channels"])):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            h = opcount.conv_out(h, 1, stride, 0)         # stride on the 1x1
            macs += opcount.conv2d_macs(h, h, c_in, c // 4, 1, 1)
            macs += opcount.conv2d_macs(h, h, c // 4, c // 4, 3, 3)
            macs += opcount.conv2d_macs(h, h, c // 4, c, 1, 1)
            if block == 0:                                # projection shortcut
                macs += opcount.conv2d_macs(h, h, c_in, c, 1, 1)
            c_in = c
    return macs + opcount.dense_macs(1, c_in, sizes["classes"])


def ops_per_sample(sizes, mix) -> int:
    return opcount.train_ops(forward_macs(sizes))


# -- traffic: learnable images from the seed --------------------------------
def _bank(seed, sizes):
    """A class is a coarse random template; the noise images are shared."""
    d, img = sizes["data"], sizes["image_size"]
    rng = np.random.default_rng([seed, 0])
    templates = rng.standard_normal(
        (sizes["classes"], d["template_px"], d["template_px"], 3),
        dtype=np.float32)
    noise = rng.standard_normal((d["noise_images"], img, img, 3),
                                dtype=np.float32) * np.float32(d["noise_scale"])
    return templates, noise


def _batch(bank, seed, index, batch, sizes):
    """An image is its class's template, upsampled, plus noise: learnable,
    so the loss must fall."""
    templates, noise = bank
    rep = sizes["image_size"] // sizes["data"]["template_px"]
    rng = np.random.default_rng([seed, 1, index])
    y = rng.integers(0, sizes["classes"], batch, dtype=np.int32)
    x = templates[y].repeat(rep, axis=1).repeat(rep, axis=2)
    x += noise[rng.integers(0, len(noise), batch)]
    return x, y


def make_pool(seed, sizes, mix, batch, n):
    """``n`` host batches, float32 NHWC images and int32 labels as
    ``ImageRecordIter``/``ToTensor`` deliver them."""
    bank = _bank(seed, sizes)
    with ThreadPoolExecutor(max_workers=min(n, 8)) as pool:
        return list(pool.map(lambda i: _batch(bank, seed, i, batch, sizes),
                             range(n)))


def check_batch(seed, sizes, mix):
    return _batch(_bank(seed, sizes), seed, 10 ** 6, sizes["check"]["batch"],
                  sizes)


# -- the plain reference ----------------------------------------------------
def reference(params, x, y, sizes):
    """``(loss, logits)`` in float32 at the highest matmul precision, batch
    statistics as in training.  ``params`` maps ``collect_params()`` names
    to arrays."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32

    def p(name):
        return jnp.asarray(params[name], f32)

    def conv(x, prefix, stride, pad):
        out = lax.conv_general_dilated(
            x, p(prefix + ".weight"), (stride, stride), [(pad, pad)] * 2,
            dimension_numbers=("NHWC", "OHWI", "NHWC"),
            precision=lax.Precision.HIGHEST)
        bias = prefix + ".bias"
        return out + p(bias) if bias in params else out

    def bn(x, prefix):
        mean = x.mean((0, 1, 2))
        var = ((x - mean) ** 2).mean((0, 1, 2))
        return ((x - mean) * lax.rsqrt(var + BN_EPS) * p(prefix + ".gamma")
                + p(prefix + ".beta"))

    x = jnp.asarray(x, f32)
    x = jax.nn.relu(bn(conv(x, "features.0", 2, 3), "features.1"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for stage, blocks in enumerate(sizes["stage_blocks"]):
        for block in range(blocks):
            at = f"features.{4 + stage}.{block}"
            stride = 2 if stage > 0 and block == 0 else 1
            h = jax.nn.relu(bn(conv(x, at + ".body.0", stride, 0),
                               at + ".body.1"))
            h = jax.nn.relu(bn(conv(h, at + ".body.3", 1, 1), at + ".body.4"))
            h = bn(conv(h, at + ".body.6", 1, 0), at + ".body.7")
            if at + ".downsample.0.weight" in params:
                x = bn(conv(x, at + ".downsample.0", stride, 0),
                       at + ".downsample.1")
            x = jax.nn.relu(h + x)
    x = x.mean((1, 2))
    logits = jnp.dot(x, p("output.weight").T,
                     precision=lax.Precision.HIGHEST) + p("output.bias")
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp, jnp.asarray(y)[:, None], axis=-1).mean()
    return loss, logits
