"""``gluon.model_zoo.olmo_hybrid`` and what it is built from: the
``GatedDeltaNet`` block, the full-attention block with its QK-norm, the
shares by heads, and the whole model through ``Trainer.compile_step``.  CPU,
toy widths; the comparison with the plain reference at the configuration's
own tolerances is ``tests/perfbench/test_reference_olmo_hybrid.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import olmo_hybrid as olmo
from mxnet_tpu.ops import contrib
from mxnet_tpu.ops import pallas_kernels as pk
from perfbench import manifest

TOY = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=4,
           num_attention_heads=8, num_key_value_heads=8, head_dim=16,
           linear_num_key_heads=8, linear_num_value_heads=8,
           linear_key_head_dim=8, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
           layer_types=["linear_attention"] * 3 + ["full_attention"]
           + ["linear_attention"] * 4,
           rms_norm_eps=1e-6, vocab_size=128, hidden_act="silu")
HELD = (0, 1, 2, 3)
REFERENCE = manifest.load_module("configs", "olmo_hybrid_7b_tp2")


def _tokens(seed, batch=2, seq=20, vocab=128):
    ids = np.random.default_rng(seed).integers(0, vocab, (batch, seq + 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


def _noise_on_every_vector(net, seed=0, scale=0.1):
    rng = np.random.default_rng(seed)
    for p in net.collect_params().values():
        if len(p.shape) == 1:
            value = p.data().asnumpy()
            p.set_data(value + scale * rng.standard_normal(value.shape)
                       .astype(value.dtype))


@pytest.fixture(scope="module")
def model():
    mx.random.seed(3)
    net = olmo.olmo_hybrid(TOY, held_heads=HELD, init_std=0.1,
                           rescale_layers=32, chunk_size=8)
    net.initialize()
    _noise_on_every_vector(net)
    return net


def test_the_model_is_built_from_its_config_keys(model):
    shapes = {n: p.shape for n, p in model.collect_params().items()}
    # four of eight heads: every projection of both mixers is the held
    # heads' alone; the feed-forward and the vocabulary are whole
    for name, shape in {
            "model.layers.0.mixer.q_proj.weight": (32, 64),
            "model.layers.0.mixer.k_proj.weight": (32, 64),
            "model.layers.0.mixer.v_proj.weight": (64, 64),
            "model.layers.0.mixer.g_proj.weight": (64, 64),
            "model.layers.0.mixer.a_proj.weight": (4, 64),
            "model.layers.0.mixer.b_proj.weight": (4, 64),
            "model.layers.0.mixer.o_proj.weight": (64, 64),
            "model.layers.0.mixer.q_conv": (32, 4),
            "model.layers.0.mixer.v_conv": (64, 4),
            "model.layers.0.mixer.A_log": (4,),
            "model.layers.0.mixer.dt_bias": (4,),
            "model.layers.0.mixer.o_norm": (16,),
            "model.layers.3.mixer.q_proj.weight": (64, 64),
            "model.layers.3.mixer.o_proj.weight": (64, 64),
            "model.layers.3.mixer.q_norm.gamma": (64,),
            "model.layers.3.mixer.k_norm.gamma": (64,),
            "model.layers.3.mlp.gate_up_proj.weight": (192, 64),
            "model.layers.3.post_feedforward_layernorm.gamma": (64,),
            "lm_head.weight": (128, 64)}.items():
        assert shapes[name] == shape, name
    assert not [n for n in shapes if "bias" in n and "dt_bias" not in n]
    # layer_types is read up to num_hidden_layers: three and one
    kinds = [type(layer.mixer).__name__ for layer in model.model.layers]
    assert kinds == ["GatedDeltaNet"] * 3 + ["OlmoHybridAttention"]
    assert model.model.layers[0].mixer.held_heads == HELD
    x, _ = _tokens(0)
    assert model(mx.nd.array(x)).shape == (2, 20, 128)
    # the initial gates are the library's: A in (0, 16), dt in [1e-3, 0.1]
    fresh = nn.GatedDeltaNet(64, 8, 8, 16)
    fresh.initialize()
    rate = np.exp(fresh.A_log.data().asnumpy())
    dt = np.log1p(np.exp(fresh.dt_bias.data().asnumpy()))
    assert rate.min() > 0 and rate.max() < 16
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    with pytest.raises(ValueError, match="held_heads"):
        olmo.olmo_hybrid(TOY, held_heads=(0, 0))
    with pytest.raises(ValueError, match="held_heads"):
        olmo.olmo_hybrid(TOY, held_heads=(8,))
    with pytest.raises(ValueError, match="layer_types"):
        olmo.olmo_hybrid({**TOY, "layer_types": ["windowed"] * 4})
    with pytest.raises(ValueError, match="hidden_act"):
        olmo.olmo_hybrid({**TOY, "hidden_act": "gelu"})


def _sizes():
    return {**TOY, "num_attention_heads": 4, "linear_num_value_heads": 4}


def test_logits_loss_and_gradients_equal_the_plain_reference(model):
    """Float32 against float32 on seeded weights with noise on every
    vector: the logits, the loss, and the gradient of one parameter of each
    kind, the rule's chunks against the reference's token-by-token state."""
    x, y = _tokens(1)
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    with mx.autograd.record():
        logits = model(mx.nd.array(x))
        loss = ce(logits, mx.nd.array(y)).mean()
    loss.backward()
    params = {n: jnp.asarray(p.data().asnumpy())
              for n, p in model.collect_params().items()}
    with jax.default_matmul_precision("highest"):
        (ref_loss, ref_logits), grads = jax.value_and_grad(
            lambda p: REFERENCE.reference(p, x, y, _sizes()),
            has_aux=True)(params)
    scale = float(jnp.max(jnp.abs(ref_logits)))
    np.testing.assert_allclose(logits.asnumpy(), ref_logits,
                               atol=2e-5 * scale)
    np.testing.assert_allclose(float(loss.asnumpy()), float(ref_loss),
                               rtol=1e-5)
    for name in ("model.embed_tokens.weight", "lm_head.weight",
                 "model.layers.0.mixer.q_proj.weight",
                 "model.layers.0.mixer.k_proj.weight",
                 "model.layers.1.mixer.v_proj.weight",
                 "model.layers.1.mixer.g_proj.weight",
                 "model.layers.2.mixer.a_proj.weight",
                 "model.layers.2.mixer.b_proj.weight",
                 "model.layers.0.mixer.o_proj.weight",
                 "model.layers.0.mixer.k_conv", "model.layers.1.mixer.v_conv",
                 "model.layers.1.mixer.A_log", "model.layers.2.mixer.dt_bias",
                 "model.layers.2.mixer.o_norm",
                 "model.layers.3.mixer.q_proj.weight",
                 "model.layers.3.mixer.q_norm.gamma",
                 "model.layers.3.mixer.k_norm.gamma",
                 "model.layers.3.mixer.o_proj.weight",
                 "model.layers.0.post_attention_layernorm.gamma",
                 "model.layers.3.post_feedforward_layernorm.gamma",
                 "model.layers.2.mlp.gate_up_proj.weight",
                 "model.layers.2.mlp.down_proj.weight", "model.norm.gamma"):
        got = model.collect_params()[name].grad().asnumpy()
        want = np.asarray(grads[name])
        assert np.abs(want).max() > 0, name
        np.testing.assert_allclose(got, want, atol=5e-4 * np.abs(want).max(),
                                   err_msg=name)


def _copy_heads(part, whole, heads, widths):
    """``part``'s weights from ``whole``'s: the rows of the ``heads`` in
    every projection into heads (``widths``: parameter -> a head's rows),
    their columns of ``o_proj``; what is not a head's is copied whole."""
    rows = {w: np.concatenate([np.arange(h * w, (h + 1) * w) for h in heads])
            for w in set(widths.values())}
    src = whole.collect_params()
    for name, p in part.collect_params().items():
        value = src[name].data().asnumpy()
        if name == "o_proj.weight":
            value = value[:, rows[widths[name]]]
        elif name in widths:
            value = value[rows[widths[name]]]
        p.set_data(value)


def test_the_two_shares_add_up_to_the_uncut_delta_net():
    """``GatedDeltaNet`` with heads 0-3 and with heads 4-7 of one uncut
    layer's weights: heads are independent up to ``o_proj``'s sum, so the
    two outputs add up to the uncut layer's."""
    mx.random.seed(5)
    init = mx.initializer.Normal(0.2)

    def layer(held):
        net = nn.GatedDeltaNet(32, 8, 8, 16, chunk_size=8,
                               allow_neg_eigval=True, held_heads=held,
                               weight_initializer=init)
        net.initialize()
        return net

    whole = layer(None)
    whole.o_norm.set_data(1.0 + 0.1 * np.random.default_rng(0)
                          .standard_normal(16).astype(np.float32))
    x = mx.nd.array(np.random.default_rng(2).standard_normal((2, 21, 32)))
    widths = {"q_proj.weight": 8, "k_proj.weight": 8, "v_proj.weight": 16,
              "g_proj.weight": 16, "a_proj.weight": 1, "b_proj.weight": 1,
              "q_conv": 8, "k_conv": 8, "v_conv": 16, "A_log": 1,
              "dt_bias": 1, "o_proj.weight": 16}
    total = 0.0
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        part = layer(held)
        assert part.held_heads == held
        _copy_heads(part, whole, held, widths)
        total = total + part(x).asnumpy()
    want = whole(x).asnumpy()
    np.testing.assert_allclose(total, want, atol=2e-6 * np.abs(want).max())


class _GivenMeanSquare(nn.RMSNorm):
    """An RMS norm that is GIVEN its statistic: what a tensor-parallel rank
    has after the all-reduce of one scalar a token."""

    def __init__(self, mean_square, epsilon, width):
        super().__init__(epsilon=epsilon, in_channels=width)
        self._mean_square = mean_square

    def forward(self, x):
        return x / mx.nd.sqrt(self._mean_square + self._epsilon) \
            * self.gamma.data(x.ctx)


def test_the_two_shares_add_up_to_the_uncut_full_layer():
    """The same for the full layer, when both shares are given the WHOLE
    projection's mean square for QK-norm (a share's own statistic is over
    its 4 heads: the configuration's departure); with their own statistic
    the shares do not add up."""
    mx.random.seed(6)
    init = mx.initializer.Normal(0.2)

    def layer(held):
        net = olmo.OlmoHybridAttention(32, 8, 16, held_heads=held,
                                       weight_initializer=init)
        net.initialize()
        return net

    whole = layer(None)
    rng = np.random.default_rng(1)
    for norm in (whole.q_norm, whole.k_norm):
        norm.gamma.set_data(1.0 + 0.1 * rng.standard_normal(128)
                            .astype(np.float32))
    x = mx.nd.array(rng.standard_normal((2, 21, 32)))
    statistic = {name: (getattr(whole, name)(x) ** 2).mean(axis=-1,
                                                          keepdims=True)
                 for name in ("q_proj", "k_proj")}
    widths = {name: 16 for name in (
        "q_proj.weight", "k_proj.weight", "v_proj.weight", "q_norm.gamma",
        "k_norm.gamma", "o_proj.weight")}
    given, own = 0.0, 0.0
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        part = layer(held)
        _copy_heads(part, whole, held, widths)
        own = own + part(x).asnumpy()
        for proj, norm in (("q_proj", "q_norm"), ("k_proj", "k_norm")):
            told = _GivenMeanSquare(statistic[proj], 1e-6, 64)
            told.initialize()
            told.gamma.set_data(getattr(part, norm).gamma.data())
            setattr(part, norm, told)
        given = given + part(x).asnumpy()
    want = whole(x).asnumpy()
    np.testing.assert_allclose(given, want, atol=2e-6 * np.abs(want).max())
    assert np.abs(own - want).max() > 1e-3 * np.abs(want).max()


def test_compile_step_one_dispatch_a_step_and_no_retrace(monkeypatch):
    monkeypatch.setenv("MXNET_SPMD_MESH", "off")    # one chip, as the cell
    mx.random.seed(7)
    net = olmo.olmo_hybrid(TOY, held_heads=HELD, recompute_layers=True,
                           init_std=0.05, rescale_layers=4, chunk_size=8)
    net.initialize()
    net.hybridize()
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                               {"learning_rate": 3e-3}, kvstore="tpu")
    step = trainer.compile_step(net, lambda n, x, y: ce(n(x), y).mean())
    x, y = (mx.nd.array(t) for t in _tokens(2, batch=2, seq=20))
    seq0 = max((e["seq"] for e in mx.telemetry.events("fallback")), default=0)
    base = mx.telemetry.snapshot()
    losses = [float(step(x, y, batch_size=2).asnumpy()) for _ in range(3)]
    # three delta-rule sites, each counted once a trace
    assert mx.telemetry.delta(base)["linear_attention.chunked"] == 3
    dispatches, traces = mx.cached_step.dispatch_count(), \
        mx.cached_step.trace_count()
    losses += [float(step(x, y, batch_size=2).asnumpy()) for _ in range(12)]
    assert mx.cached_step.dispatch_count() - dispatches == 12
    assert mx.cached_step.trace_count() == traces
    assert step.last_step_compiled
    assert losses[-1] < losses[0] - 0.5 and all(map(np.isfinite, losses))
    assert not [e for e in mx.telemetry.events("fallback")
                if e["seq"] > seq0]
    # neither A_log nor dt_bias takes weight decay
    assert net.model.layers[0].mixer.A_log.wd_mult == 0.0
    assert net.model.layers[0].mixer.dt_bias.wd_mult == 0.0


def test_under_amp_the_stream_is_bf16_and_the_gates_are_float32():
    mx.random.seed(8)
    net = nn.GatedDeltaNet(32, 4, 8, 16, chunk_size=8,
                           allow_neg_eigval=True)
    net.initialize()
    x = mx.nd.array(np.random.default_rng(3).standard_normal((1, 16, 32)))
    want = net(x).asnumpy()
    mx.amp.init("bfloat16")
    try:
        got = net(x.astype("bfloat16"))
        assert got.dtype == jnp.bfloat16
        # float32 decay sums, inverse and state inside: the bf16 result is
        # within bf16 rounding of the float32 one
        assert np.abs(got.asnumpy().astype(np.float32) - want).max() \
            < 2 ** -5 * np.abs(want).max()
    finally:
        mx.amp.uninit()


def test_the_full_layer_takes_the_kernels_at_heads_of_128(monkeypatch):
    """On a TPU trace with no mesh the full layer's core is the grouped
    causal kernels at as many key-value heads as query heads (Pallas
    interpreter here), and says nothing falls back."""
    monkeypatch.setattr(contrib, "_attention_platform", lambda: "tpu")
    monkeypatch.setattr(pk, "_BLOCK", 16)
    mx.random.seed(9)
    net = olmo.OlmoHybridAttention(32, 4, 128, held_heads=(0, 1),
                                   weight_initializer=mx.initializer
                                   .Normal(0.2))
    net.initialize()
    x = mx.nd.array(np.random.default_rng(4).standard_normal((1, 32, 32)))
    base = mx.telemetry.snapshot()
    seq0 = max((e["seq"] for e in mx.telemetry.events("fallback")), default=0)
    got = net(x).asnumpy()
    assert mx.telemetry.delta(base)["attention.fused"] == 1
    assert not [e for e in mx.telemetry.events("fallback")
                if e["seq"] > seq0]
    monkeypatch.setattr(contrib, "_attention_platform", lambda: "cpu")
    np.testing.assert_allclose(got, net(x).asnumpy(),
                               atol=2e-5 * np.abs(got).max())
