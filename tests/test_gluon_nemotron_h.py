"""``gluon.model_zoo.nemotron_h``: blocks built from a pattern string,
trained through ``Trainer.compile_step`` as one dispatch a step."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import nemotron_h as nh
from mxnet_tpu.parallel.moe import HELD_STATS

CONFIG = dict(
    vocab_size=128, hidden_size=32, hybrid_override_pattern="ME*E",
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, intermediate_size=48,
    n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5,
    layer_norm_epsilon=1e-5, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4)
KINDS = {"M": nh.NemotronHMamba2Mixer, "E": nh.NemotronHMoE,
         "*": nh.NemotronHAttention}


def _net(pattern="ME*E", **overrides):
    mx.random.seed(0)
    net = nh.nemotron_h({**CONFIG, "hybrid_override_pattern": pattern},
                        **overrides)
    net.initialize()
    net.hybridize()
    return net


def _batch(batch=2, seq=20, seed=0):
    x = np.random.default_rng(seed).integers(0, 128, (batch, seq + 1))
    return (mx.nd.array(x[:, :-1].astype(np.int32)),
            mx.nd.array(x[:, 1:].astype(np.int32)))


@pytest.mark.parametrize("pattern", ["M", "E", "*", "MEMEM*EME", "*EM",
                                     "EEMM**"])
def test_any_pattern_builds_one_mixer_a_layer(pattern):
    net = _net(pattern)
    layers = list(net.backbone.layers)
    assert [type(l.mixer) for l in layers] == [KINDS[k] for k in pattern]
    x, _ = _batch()
    out = net(x)
    assert out.shape == (2, 20, 128) and np.isfinite(out.asnumpy()).all()
    names = list(net.collect_params())
    assert names[0] == "backbone.embeddings.weight"
    assert names[-1] == "lm_head.weight"
    assert not [n for n in names if n.endswith(".bias")]      # none anywhere
    assert len([n for n in names if n.endswith("conv_bias")]) \
        == pattern.count("M")                 # but the convolution's


def test_an_unknown_layer_kind_is_refused():
    with pytest.raises(ValueError, match="none of M, E"):
        nh.nemotron_h({**CONFIG, "hybrid_override_pattern": "ME-"})


def test_initial_values_follow_the_public_code():
    net = _net("ME")
    p = {n: v.data().asnumpy() for n, v in net.collect_params().items()}
    at = "backbone.layers.0.mixer."
    np.testing.assert_allclose(p[at + "A_log"], np.log(np.arange(1, 5)),
                               rtol=1e-6)
    np.testing.assert_array_equal(p[at + "D"], np.ones(4, np.float32))
    dt = np.log1p(np.exp(p[at + "dt_bias"]))          # softplus
    assert (dt > 0.00099).all() and (dt < 0.1001).all()
    assert abs(p[at + "conv_weight"]).max() <= 0.5
    # rescale_prenorm_residual: the projection into the stream is smaller
    assert p[at + "out_proj.weight"].std() < 0.8 * p[at + "in_proj.weight"].std()
    bias = p["backbone.layers.1.mixer.e_score_correction_bias"]
    assert 0 < abs(bias).max() <= 0.05
    assert net.collect_params()[
        "backbone.layers.1.mixer.e_score_correction_bias"].grad_req == "null"


@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_trains_as_one_dispatch_a_step_without_retrace(amp, monkeypatch):
    monkeypatch.setenv("MXNET_SPMD_MESH", "off")
    if amp:
        mx.amp.init(amp)
    try:
        net = _net("MEMEM*EME", held_experts=(0, 1, 2, 3))
        ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
        trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                                   {"learning_rate": 1e-2}, kvstore="tpu")
        step = trainer.compile_step(net, lambda n, x, y: ce(n(x), y).mean())
        x, y = _batch()
        first = float(step(x, y, batch_size=2).asnumpy())
        cs = mx.cached_step
        base = (cs.dispatch_count(), cs.trace_count(),
                cs.deferred_read_count())
        seq = max((e["seq"] for e in mx.telemetry.events("fallback")),
                  default=0)
        moe0 = mx.telemetry.snapshot()["moe.steps"]
        for _ in range(10):
            loss = step(x, y, batch_size=2)
        last = float(loss.asnumpy())
        assert cs.dispatch_count() - base[0] == 10
        assert cs.trace_count() == base[1]
        assert cs.deferred_read_count() == base[2]
        assert step.last_step_compiled
        assert last < first - 1.0
        assert not [e for e in mx.telemetry.events("fallback")
                    if e["seq"] > seq]
        # four expert layers counted ten steps each, on the device
        assert mx.telemetry.snapshot()["moe.steps"] - moe0 == 40
        if amp:      # the residual stream follows the activations' type
            with mx.autograd.predict_mode():
                assert net.backbone(x).dtype == mx.amp.target_dtype()
    finally:
        if amp:
            mx.amp.uninit()


def test_recomputed_layers_give_the_same_step(monkeypatch):
    monkeypatch.setenv("MXNET_SPMD_MESH", "off")
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _batch(seed=3)
    losses = []
    for recompute in (False, True):
        net = _net("ME*", recompute_layers=recompute)
        trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                                   {"learning_rate": 1e-2}, kvstore="tpu")
        step = trainer.compile_step(net, lambda n, x, y: ce(n(x), y).mean())
        losses.append([float(step(x, y, batch_size=2).asnumpy())
                       for _ in range(4)])
        counts = net.collect_params()[
            "backbone.layers.1.mixer.counts"].data().asnumpy()
        # written through the remat
        assert counts[HELD_STATS.index("steps")] == 4
    np.testing.assert_allclose(losses[0], losses[1], rtol=2e-5)
