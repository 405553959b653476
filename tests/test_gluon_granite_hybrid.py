"""``gluon.model_zoo.granite_hybrid`` and what it is built from: Nemotron-H's
Mamba-2 mixer and attention block told which heads they hold, the tied head,
the multipliers, the shares by heads, and the whole model through
``Trainer.compile_step``.  CPU, toy widths; the comparison with the plain
reference at the configuration's own tolerances is
``tests/perfbench/test_reference_granite_hybrid.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import granite_hybrid as granite
from mxnet_tpu.gluon.model_zoo import nemotron_h as nh
from mxnet_tpu.ops import contrib
from mxnet_tpu.ops import pallas_kernels as pk
from perfbench import manifest

TOY = dict(hidden_size=64, shared_intermediate_size=96, num_hidden_layers=10,
           layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 9,
           mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1,
           mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=8,
           num_attention_heads=4, num_key_value_heads=2,
           attention_multiplier=1 / 16, embedding_multiplier=12,
           residual_multiplier=0.22, logits_scaling=8, rms_norm_eps=1e-5,
           vocab_size=128, tie_word_embeddings=True,
           position_embedding_type="nope", hidden_act="silu",
           num_local_experts=0)
HELD = (0, 1, 2, 3)                 # Mamba-2 heads; query heads 0-1, kv 0
REFERENCE = manifest.load_module("configs", "granite_4_0_h_micro_tp2")


def _sizes(**kw):
    """The reference's sizes for the toy model holding ``HELD``."""
    return {**TOY, "mamba_n_heads": 4, "num_attention_heads": 2,
            "num_key_value_heads": 1,
            "published": {"num_attention_heads": 4}, **kw}


def _tokens(seed, batch=2, seq=20, vocab=128):
    ids = np.random.default_rng(seed).integers(0, vocab, (batch, seq + 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


def _scale_matrices(net, factor):
    """Every projection and the embedding ``factor`` times their initial
    value (the convolution's taps keep theirs): toy widths need larger
    weights than the models' normal of 0.02 for a softmax or a gate to
    tell one term from another."""
    for name, p in net.collect_params().items():
        if name.endswith(".weight"):
            p.set_data(p.data().asnumpy() * factor)


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
    net = granite.granite_hybrid(TOY, held_heads=HELD, rescale_layers=10)
    net.initialize()
    _scale_matrices(net, 5.0)
    _noise_on_every_vector(net)
    return net


def _params(net):
    return {n: jnp.asarray(p.data().asnumpy())
            for n, p in net.collect_params().items()}


def test_the_model_is_built_from_its_config_keys(model):
    shapes = {n: p.shape for n, p in model.collect_params().items()}
    # four of eight Mamba-2 heads of 16 (z, x and dt of the held heads, the
    # ONE group's B and C of 16 whole), two of four query heads over one of
    # two key-value heads; the feed-forward and the vocabulary are whole
    for name, shape in {
            "model.layers.0.mixer.in_proj.weight": (64 + 64 + 32 + 4, 64),
            "model.layers.0.mixer.conv_weight": (96, 4),
            "model.layers.0.mixer.conv_bias": (96,),
            "model.layers.0.mixer.A_log": (4,),
            "model.layers.0.mixer.dt_bias": (4,),
            "model.layers.0.mixer.D": (4,),
            "model.layers.0.mixer.norm_weight": (64,),
            "model.layers.0.mixer.out_proj.weight": (64, 64),
            "model.layers.5.mixer.q_proj.weight": (32, 64),
            "model.layers.5.mixer.k_proj.weight": (16, 64),
            "model.layers.5.mixer.v_proj.weight": (16, 64),
            "model.layers.5.mixer.o_proj.weight": (64, 32),
            "model.layers.5.mlp.gate_up_proj.weight": (192, 64),
            "model.layers.5.mlp.down_proj.weight": (64, 96),
            "model.layers.9.post_attention_layernorm.gamma": (64,),
            "model.embed_tokens.weight": (128, 64)}.items():
        assert shapes[name] == shape, name
    # the head is the embedding: no weight of its own, no bias anywhere
    assert not [n for n in shapes if "lm_head" in n]
    assert not [n for n in shapes if n.endswith("bias")
                and not n.endswith(("dt_bias", "conv_bias"))]
    # layer_types is read up to num_hidden_layers: 5, 1, 4
    kinds = [type(layer.mixer).__name__ for layer in model.model.layers]
    assert kinds == ["NemotronHMamba2Mixer"] * 5 + ["NemotronHAttention"] \
        + ["NemotronHMamba2Mixer"] * 4
    assert model.model.layers[0].mixer.held_heads == HELD
    assert model.model.layers[5].mixer.held_heads == (0, 1)
    x, _ = _tokens(0)
    assert model(mx.nd.array(x)).shape == (2, 20, 128)
    # the held heads take their own entries of A_log: log(id + 1)
    share = nh.NemotronHMamba2Mixer(64, 8, 16, 1, 16, held_heads=(4, 5, 6, 7))
    share.initialize()
    np.testing.assert_allclose(share.A_log.data().asnumpy(),
                               np.log([5.0, 6.0, 7.0, 8.0]), rtol=1e-6)
    for bad in ((0, 0), (8,), (0, 1, 2)):          # the last: half a head
        with pytest.raises(ValueError, match="held_heads"):
            granite.granite_hybrid(TOY, held_heads=bad)
    with pytest.raises(ValueError, match="layer_types"):
        granite.granite_hybrid({**TOY, "layer_types": ["moe"] * 10})
    for key, value in (("tie_word_embeddings", False),
                       ("position_embedding_type", "rope"),
                       ("num_local_experts", 8)):
        with pytest.raises(ValueError, match=key):
            granite.granite_hybrid({**TOY, key: value})
    with pytest.raises(ValueError, match="ONE group"):
        nh.NemotronHMamba2Mixer(64, 8, 16, 2, 16, held_heads=HELD)


def test_logits_loss_and_gradients_equal_the_plain_reference(model):
    """Float32 against float32 on seeded weights with noise on every
    vector: the logits, the loss, and the gradient of one parameter of each
    kind, the scan's chunks against the reference's token-by-token state."""
    x, y = _tokens(1)
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    with mx.autograd.record():
        logits = model(mx.nd.array(x))
        loss = ce(logits, mx.nd.array(y)).mean()
    loss.backward()
    with jax.default_matmul_precision("highest"):
        (ref_loss, ref_logits), grads = jax.value_and_grad(
            lambda p: REFERENCE.reference(p, x, y, _sizes()),
            has_aux=True)(_params(model))
    scale = float(jnp.max(jnp.abs(ref_logits)))
    np.testing.assert_allclose(logits.asnumpy(), ref_logits,
                               atol=2e-5 * scale)
    np.testing.assert_allclose(float(loss.asnumpy()), float(ref_loss),
                               rtol=1e-5)
    for name in ("model.embed_tokens.weight",
                 "model.layers.0.mixer.in_proj.weight",
                 "model.layers.1.mixer.conv_weight",
                 "model.layers.2.mixer.conv_bias",
                 "model.layers.3.mixer.dt_bias", "model.layers.4.mixer.A_log",
                 "model.layers.6.mixer.D", "model.layers.7.mixer.norm_weight",
                 "model.layers.8.mixer.out_proj.weight",
                 "model.layers.5.mixer.q_proj.weight",
                 "model.layers.5.mixer.k_proj.weight",
                 "model.layers.5.mixer.v_proj.weight",
                 "model.layers.5.mixer.o_proj.weight",
                 "model.layers.0.input_layernorm.gamma",
                 "model.layers.5.post_attention_layernorm.gamma",
                 "model.layers.9.mlp.gate_up_proj.weight",
                 "model.layers.9.mlp.down_proj.weight", "model.norm.gamma"):
        got = model.collect_params()[name].grad().asnumpy()
        want = np.asarray(grads[name])
        assert np.abs(want).max() > 0, name
        np.testing.assert_allclose(got, want, atol=5e-4 * np.abs(want).max(),
                                   err_msg=name)


def test_the_tied_head_is_one_parameter_whose_gradient_adds_both_uses(model):
    """One ``Parameter`` serves the lookup and the head, and its gradient is
    the embedding's part plus the head's: the reference with the two uses
    given apart (a head of its own) says what each part is."""
    x, y = _tokens(2)
    params = model.collect_params()
    weight = params["model.embed_tokens.weight"]
    assert [n for n, p in params.items() if p is weight] \
        == ["model.embed_tokens.weight"]
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    with mx.autograd.record():
        loss = ce(model(mx.nd.array(x)), mx.nd.array(y)).mean()
    loss.backward()
    got = weight.grad().asnumpy()
    p = _params(model)
    with jax.default_matmul_precision("highest"):
        lookup, head = jax.grad(
            lambda e, h: REFERENCE.reference(
                {**p, "model.embed_tokens.weight": e, "lm_head.weight": h},
                x, y, _sizes())[0], argnums=(0, 1))(
            p["model.embed_tokens.weight"], p["model.embed_tokens.weight"])
    scale = np.abs(got).max()
    np.testing.assert_allclose(got, lookup + head, atol=5e-4 * scale)
    for part in (lookup, head):               # neither use alone
        assert np.abs(got - np.asarray(part)).max() > 0.05 * scale


def _copy_rows(part, whole, picks):
    """``part``'s weights from ``whole``'s: ``picks`` maps a parameter name
    to (axis, indices) of ``whole``'s value it holds; anything else is
    copied whole."""
    src = whole.collect_params()
    for name, p in part.collect_params().items():
        value = src[name].data().asnumpy()
        if name in picks:
            axis, index = picks[name]
            value = np.take(value, index, axis=axis)
        p.set_data(value)


def _heads(ids, width):
    return np.concatenate([np.arange(h * width, (h + 1) * width)
                           for h in ids])


def _mamba_picks(held, heads, p, n):
    """What a share of ``held`` heads holds of an uncut layer's mixer:
    ``in_proj`` rows z, x of its heads, the whole B and C, dt of its heads;
    their convolution channels (x's, and B's and C's whole)."""
    inner = heads * p
    x_rows = _heads(held, p)
    conv = np.concatenate([x_rows, inner + np.arange(2 * n)])
    rows = np.concatenate([x_rows, inner + conv,
                           2 * inner + 2 * n + np.asarray(held)])
    one = np.asarray(held)
    return {"in_proj.weight": (0, rows), "conv_weight": (0, conv),
            "conv_bias": (0, conv), "dt_bias": (0, one), "A_log": (0, one),
            "D": (0, one), "norm_weight": (0, x_rows),
            "out_proj.weight": (1, x_rows)}


def test_the_two_shares_add_up_to_the_uncut_mamba_mixer(monkeypatch):
    """Heads 0-3 and heads 4-7 of one uncut mixer's weights (one group of
    B and C, counted once in the uncut layer and held whole by both shares):
    when both shares are given the WHOLE width's mean square for the gated
    norm (what a rank has after the all-reduce of one scalar a token), the
    two outputs add up to the uncut layer's; with their own statistic (the
    configuration's departure) they do not."""
    from mxnet_tpu.gluon.model_zoo import nemotron_h

    mx.random.seed(5)
    heads, p, n, hidden = 8, 16, 16, 32

    def mixer(held):
        net = nh.NemotronHMamba2Mixer(hidden, heads, p, 1, n, 4, 8,
                                      held_heads=held)
        net.initialize()
        return net

    whole = mixer(None)
    _scale_matrices(whole, 10.0)
    _noise_on_every_vector(whole, seed=1)
    rows = {name: p.shape[0] for name, p in
            (("in", whole.in_proj.weight), ("conv", whole.conv_weight))}
    assert rows == {"in": 2 * heads * p + 2 * n + heads,
                    "conv": heads * p + 2 * n}
    x = mx.nd.array(np.random.default_rng(2).standard_normal((2, 21, hidden)))
    seen, given = [], {}
    real = nemotron_h.invoke

    def invoke(op, inputs, attrs):
        if op != "GatedRMSNorm":
            return real(op, inputs, attrs)
        y, z, w = inputs
        gated = y * mx.nd.sigmoid(z) * z
        seen.append((gated ** 2).mean(axis=-1, keepdims=True))
        ms = given.get("ms", seen[-1])
        return gated / mx.nd.sqrt(ms + attrs["eps"]) * w

    monkeypatch.setattr(nemotron_h, "invoke", invoke)
    want = whole(x).asnumpy()
    whole_ms = seen[-1]                       # the uncut width's statistic
    told, own, in_rows = 0.0, 0.0, 0
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        part = mixer(held)
        assert part.held_heads == held
        _copy_rows(part, whole, _mamba_picks(held, heads, p, n))
        in_rows += part.in_proj.weight.shape[0]
        given["ms"] = whole_ms
        told = told + part(x).asnumpy()
        given.clear()
        own = own + part(x).asnumpy()
    # B and C once in the uncut layer, once in each share
    assert in_rows == rows["in"] + 2 * n
    np.testing.assert_allclose(told, want, atol=2e-5 * np.abs(want).max())
    assert np.abs(own - want).max() > 1e-3 * np.abs(want).max()
    # the plain operator is what the stand-in replaced, where nothing is given
    monkeypatch.setattr(nemotron_h, "invoke", real)
    np.testing.assert_allclose(whole(x).asnumpy(), want,
                               atol=2e-5 * np.abs(want).max())


def test_the_two_shares_add_up_to_the_uncut_attention_layer():
    """Query heads 0-3 over key-value head 0 and 4-7 over 1 (groups of four)
    of one uncut layer's weights: the two outputs add up to the uncut
    layer's, at Granite's scale of the scores."""
    mx.random.seed(6)
    d, hidden = 16, 32

    def layer(held):
        net = nh.NemotronHAttention(hidden, 8, 2, d, held_heads=held,
                                    scale=1 / 16)
        net.initialize()
        return net

    whole = layer(None)
    _scale_matrices(whole, 15.0)
    x = mx.nd.array(np.random.default_rng(3).standard_normal((2, 21, hidden)))
    want = whole(x).asnumpy()
    total = 0.0
    for held, kv in (((0, 1, 2, 3), (0,)), ((4, 5, 6, 7), (1,))):
        part = layer(held)
        _copy_rows(part, whole, {
            "q_proj.weight": (0, _heads(held, d)),
            "k_proj.weight": (0, _heads(kv, d)),
            "v_proj.weight": (0, _heads(kv, d)),
            "o_proj.weight": (1, _heads(held, d))})
        total = total + part(x).asnumpy()
    np.testing.assert_allclose(total, want, atol=2e-5 * np.abs(want).max())
    with pytest.raises(ValueError, match="whole groups"):
        nh.NemotronHAttention(hidden, 8, 2, d, held_heads=(0, 1))


def test_compile_step_one_dispatch_a_step_and_no_retrace(monkeypatch):
    monkeypatch.setenv("MXNET_SPMD_MESH", "off")    # one chip, as the cell
    mx.random.seed(7)
    net = granite.granite_hybrid(TOY, held_heads=HELD, recompute_layers=True,
                                 rescale_layers=10)
    net.initialize()
    _scale_matrices(net, 2.5)
    net.hybridize()
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                               {"learning_rate": 3e-3}, kvstore="tpu")
    step = trainer.compile_step(net, lambda n, x, y: ce(n(x), y).mean())
    x, y = (mx.nd.array(t) for t in _tokens(2, batch=2, seq=20))
    seq0 = max((e["seq"] for e in mx.telemetry.events("fallback")), default=0)
    base = mx.telemetry.snapshot()
    losses = [float(step(x, y, batch_size=2).asnumpy()) for _ in range(3)]
    moved = mx.telemetry.delta(base)
    # nine scan sites and one attention core, each counted once a trace
    assert moved["ssm.scan_unfused"] == 9
    assert moved["attention.unfused"] == 1
    dispatches, traces = mx.cached_step.dispatch_count(), \
        mx.cached_step.trace_count()
    losses += [float(step(x, y, batch_size=2).asnumpy()) for _ in range(12)]
    assert mx.cached_step.dispatch_count() - dispatches == 12
    assert mx.cached_step.trace_count() == traces
    assert step.last_step_compiled
    assert losses[-1] < losses[0] - 0.5 and all(map(np.isfinite, losses))
    assert not [e for e in mx.telemetry.events("fallback")
                if e["seq"] > seq0]
    # one optimizer state for the tied weight, none for a head
    assert not [n for n in net.collect_params() if "lm_head" in n]
    layer = net.model.layers[0].mixer
    assert (layer.A_log.wd_mult, layer.dt_bias.wd_mult, layer.D.wd_mult) \
        == (0.0, 0.0, 0.0)


def test_the_cells_kernels_on_a_tpu_trace(monkeypatch):
    """As a TPU trace with no mesh takes them (Pallas interpreter here):
    the scan at one group of 32 heads of 64 in chunks of 256, two blocks of
    16 heads a grid step, and the attention core at 4 query heads of 64
    over 1 at Granite's scale, both counted as fused, nothing falls back;
    the same numbers as the CPU's expressions."""
    from mxnet_tpu.ops import ssm

    monkeypatch.setattr(contrib, "_attention_platform", lambda: "tpu")
    monkeypatch.setattr(ssm, "_scan_platform", lambda: "tpu")
    monkeypatch.setattr(pk, "_BLOCK", 128)
    mx.random.seed(9)
    mixer = nh.NemotronHMamba2Mixer(64, 64, 64, 1, 128, 4, 256,
                                    held_heads=range(32))
    attention = nh.NemotronHAttention(64, 8, 2, 64, held_heads=range(4),
                                      scale=1 / 64)
    x = mx.nd.array(np.random.default_rng(4).standard_normal((1, 512, 64)))
    seq0 = max((e["seq"] for e in mx.telemetry.events("fallback")), default=0)
    got = {}
    for name, net in (("scan", mixer), ("core", attention)):
        net.initialize()
        _scale_matrices(net, 2.5)
        base = mx.telemetry.snapshot()
        got[name] = net(x).asnumpy()
        moved = mx.telemetry.delta(base)
        if name == "scan":
            assert (moved["ssm.scan_fused"], moved.get("ssm.scan_unfused", 0),
                    moved["ssm.scan_head_blocks"]) == (1, 0, 2)
        else:
            assert (moved["attention.fused"],
                    moved.get("attention.unfused", 0)) == (1, 0)
    assert not [e for e in mx.telemetry.events("fallback")
                if e["seq"] > seq0]
    monkeypatch.setattr(contrib, "_attention_platform", lambda: "cpu")
    monkeypatch.setattr(ssm, "_scan_platform", lambda: "cpu")
    for name, net in (("scan", mixer), ("core", attention)):
        want = net(x).asnumpy()
        np.testing.assert_allclose(got[name], want,
                                   atol=3e-5 * np.abs(want).max(),
                                   err_msg=name)
