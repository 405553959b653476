"""Configuration ``glm_4_7_flash_ep8``: its plain reference against the Gluon
forward at toy widths on the CPU, each named term of the mathematics against
the configuration's own tolerances, the operation counts against hand
counts, the size of the cut, and the readers this configuration's cell
brings."""
import json
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from perfbench import manifest, opcount, run, scope_view

CELL = "glm47_flash_train_s8k"
DRIVER = manifest.load_module("drivers", "train_fixed_shape")


@pytest.fixture(scope="module")
def pair():
    """The program's float32 forward (no AMP) and everything the reference
    needs, at the rehearsal's widths with the check's own weights, so that
    every norm's scale is a term that shows."""
    c = manifest.resolve(CELL, rehearse=True)
    # weights 2.4 times the rehearsal's: at heads of 16 the scores are
    # otherwise so small that a softmax hardly tells 1/sqrt(12) from
    # 1/sqrt(16), where at heads of 256 it does
    cfg, sizes = c.config_module, {**c.sizes, "init_std": 0.12}
    built = cfg.build(mx, sizes)
    net = built["net"]
    x, y = cfg.check_batch(5, sizes, {"seq_len": 48})
    with mx.autograd.predict_mode():
        net(mx.nd.array(x))
    DRIVER._check_weights(net, sizes["check"], 5)
    with mx.autograd.predict_mode():
        got = net(mx.nd.array(x))
        loss = float(built["head_loss"](got, mx.nd.array(y)).asnumpy())
    params = {n: p.data().asnumpy() for n, p in net.collect_params().items()}
    return cfg, sizes, params, x, y, got._data, loss


def _compare(pair, **kw):
    import jax

    cfg, sizes, params, x, y, got, loss = pair
    sizes = {**sizes, **kw.pop("changed", {})}
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits, margins = cfg.reference_parts(
            params, x, y, sizes, **kw)
    real = manifest.resolve(CELL).sizes["check"]     # the cell's own limits
    out = cfg.compare(got, loss, float(ref_loss), ref_logits, margins,
                      {**sizes, "check": real})
    assert set(out.pop("per_position")) == {"logits_err", "margins"}
    return out


def test_reference_equals_the_gluon_forward(pair):
    out = _compare(pair)
    assert out["ok"], out
    # float32 against float32: rounding, not a tolerance's worth
    assert out["logits_err"] < 2e-4 and out["max_logits_err"] < 2e-4
    assert out["loss_err"] < 1e-5
    assert out["unexposed_outlier_share"] == 0 == out["exposed_outlier_share"]
    assert len(out["logits_err_median_by_depth"]) == 2


def test_eight_bit_operands_fail_and_bf16_operands_pass(pair):
    """The nearest precision below the configuration's: both operands of
    every product rounded to float8 (e4m3) read far over the median's
    limit; rounded to bf16, the configuration's own precision, well
    under it."""
    import jax.numpy as jnp

    low = _compare(pair, operand_dtype=jnp.float8_e4m3fn)
    assert not low["ok"] and low["logits_err"] > 2 * low["logits_tol"], low
    own = _compare(pair, operand_dtype=jnp.bfloat16)
    assert own["ok"] and own["logits_err"] < 0.5 * own["logits_tol"], own


def test_reference_signature_is_the_harnesses(pair):
    cfg, sizes, params, x, y, _, _ = pair
    loss, logits = cfg.reference(params, x, y, sizes)
    assert logits.shape == (x.shape[0], 2, x.shape[1], sizes["vocab_size"])
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError, match="without="):
        cfg.reference_parts(params, x, y, sizes, without=("rope",))


@pytest.mark.parametrize("term", [
    "q_rotary",                # rotary positions on the queries' part
    "k_rotary",                # ... and on the one rotary key
    "shared_rotary_key",       # a rotary key a head instead of one a token
    "score_scale",             # 1/sqrt(nope) instead of 1/sqrt(nope + rope)
    "q_a_layernorm",           # the query latent's norm
    "kv_a_layernorm",          # the key-value latent's norm
    "select_bias",             # the selection's + b
    "routed_scaling",          # the 1.8
    "expert_gate",             # silu(gate) of the gated experts
    "shared_expert",           # the shared expert
    "dense_layer",             # the leading dense feed-forward
    "enorm", "hnorm",          # the module's two norms
    "mtp_loss",                # lambda x the module's loss
    "mtp_shift",               # the module reads token i + 1, not token i
])
def test_a_missing_term_fails_the_configurations_tolerance(pair, term):
    assert term in pair[0].TERMS
    out = _compare(pair, without=(term,))
    assert not out["ok"], (term, out)


def test_another_lambda_fails_by_the_loss_alone(pair):
    out = _compare(pair, changed={"mtp_loss_weight": 0.1})
    assert not out["ok"] and out["loss_err"] > out["loss_tol"]
    assert out["logits_err"] < 2e-4


def test_a_position_is_exposed_by_the_references_margin_alone(pair):
    """``compare`` takes the exposed positions from the reference's margins,
    never from what the program chose; the module's depth is exposed by the
    module's own layer too."""
    import jax.numpy as jnp

    cfg, sizes, params, x, y, got, loss = pair
    ref_loss, ref_logits, margins = cfg.reference_parts(params, x, y, sizes)
    layers = sizes["num_hidden_layers"] - sizes["first_k_dense_replace"] + 1
    assert margins.shape == (layers,) + x.shape
    assert float(jnp.min(margins)) >= 0
    spec = {**manifest.resolve(CELL).sizes["check"], "tie_margin": 1.0}
    out = cfg.compare(got, loss, float(ref_loss), ref_logits, margins,
                      {**sizes, "check": spec})
    assert out["exposed_share"] == 1.0 and out["ok"]
    # a tie in the module's layer alone exposes depth 1 and not depth 0
    only_module = margins.at[:-1].set(1.0).at[-1].set(0.0)
    out = cfg.compare(got, loss, float(ref_loss), ref_logits, only_module,
                      {**sizes, "check": {**spec, "tie_margin": 0.5}})
    assert out["exposed_share"] == 0.5
    # an outlier at a position the reference does not expose counts against
    # the unexposed limit, whatever the program did there
    spec = {**spec, "tie_margin": 0.0}
    moved = got.at[0, 1, :3].add(100.0)      # under half of a block
    out = cfg.compare(moved, loss, float(ref_loss), ref_logits, margins,
                      {**sizes, "check": spec})
    assert out["exposed_share"] == 0.0 and not out["ok"]
    assert out["unexposed_outlier_share"] == pytest.approx(3 / (2 * x.size))
    assert out["logits_err"] <= out["logits_tol"]     # not by the median
    # a wrong stretch of one depth fails by its block's median
    moved = got.at[0, 0, :40].add(100.0)
    out = cfg.compare(moved, loss, float(ref_loss), ref_logits, margins,
                      {**sizes, "check": {**spec, "block": 16,
                                          "unexposed_outlier_share_max": 1}})
    assert not out["ok"] and out["logits_err"] > out["logits_tol"]


# -- operation counts against hand counts, at the published widths ------------
def test_one_layer_of_each_kind_by_hand():
    c = manifest.resolve(CELL)
    cfg, s = c.config_module, c.sizes
    # a token through latent attention: 2048 x 768 and 768 x 5120 for the
    # queries, 2048 x 576 and 512 x 8960 for keys and values, the causal
    # core 20 heads x (256 + 256) x 8192 keys at half the square, and
    # 5120 x 2048 back
    assert cfg.latent_proj_macs(s, 1) == 1_572_864 + 3_932_160 + 1_179_648 \
        + 4_587_520 == 11_272_192
    assert cfg.latent_core_macs(s, 8192) == 8192 * 20 * 512 * 4096
    assert cfg.attention_macs(s, 8192) == 8192 * (
        11_272_192 + 41_943_040 + 10_485_760) == 8192 * 63_700_992
    # the dense layer's three products of 2048 x 10240
    assert cfg.gated_macs(s, 1, s["intermediate_size"]) == 62_914_560
    # experts: router 2048 x 64, shared 3 x 2048 x 1536, and the held
    # experts at the MEAN share: 4 x 8/64 rows a token x 3 x 2048 x 1536
    assert cfg.expert_row_macs(s) == 9_437_184
    assert cfg.mean_held_rows(s, 8192) == 4096       # 512 an expert
    assert cfg.moe_macs(s, 8192) == 8192 * (131_072 + 9_437_184) \
        + 4096 * 9_437_184 == 8192 * 14_286_848
    # the whole cut: five layers and the module's, BOTH head passes of
    # 2048 x 19360, the module's projection 4096 x 2048
    head = 2048 * 19360
    per_token = 6 * 63_700_992 + 62_914_560 + 5 * 14_286_848 + 2 * head \
        + 8_388_608
    assert per_token == 604_241_920
    assert cfg.forward_macs(s, 8192) == 8192 * per_token
    assert cfg.ops_per_sample(s, c.mix) == opcount.train_ops(8192 * per_token)
    # the issue's shares: the six cores 2.06 T of 4.95 T forward MACs
    assert round(6 * cfg.latent_core_macs(s, 8192) / 1e12, 2) == 2.06
    assert round(cfg.forward_macs(s, 8192) / 1e12, 2) == 4.95


def test_the_cut_has_706518848_parameters_without_allocating_them():
    sizes = manifest.resolve(CELL).sizes
    net = mx.gluon.model_zoo.glm4_moe_lite.glm4_moe_lite(
        {**sizes, "n_routed_experts": sizes["router_experts"]},
        held_experts=range(sizes["n_routed_experts"]))
    params = net.collect_params()
    assert all(p._data is None for p in params.values())   # never initialised

    def count(prefix, but=()):
        """Model parameters under ``prefix``; the layers' device counters
        (five numbers each) are not the model's."""
        return sum(int(np.prod(p.shape)) for n, p in params.items()
                   if n.startswith(prefix) and not n.endswith(".counts")
                   and not n.startswith(but))

    assert count("model.layers.0.self_attn.") == 21_759_232
    assert count("model.layers.0.") == 84_677_888
    assert count("model.layers.1.mlp.router_weight") == 131_072
    assert count("model.layers.1.mlp.shared_expert.") == 9_437_184
    assert count("model.layers.1.mlp.experts_") == 8 * 9_437_184
    for i in (1, 2, 3, 4):
        assert count(f"model.layers.{i}.") == 106_829_120
    assert count("model.embed_tokens.") == count("lm_head.") == 39_649_280
    assert count("mtp.") == 115_223_872 == 8_388_608 + 106_829_120 + 3 * 2048
    assert count("") == 706_518_848


def test_the_file_states_the_deployment_and_the_catalogs_numbers():
    sizes = manifest.resolve(CELL).sizes
    row = None
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            row = next(json.loads(l) for l in f if sizes["source"] in l)
    except OSError:
        pytest.skip("the catalog is not on this machine")
    for key, value in row["config"].items():
        if key in sizes["reduced"]:
            assert sizes["published"][key] == value
        else:
            assert sizes[key] == value, key
    assert sizes["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert sizes["router_experts"] == row["config"]["n_routed_experts"]
    assert sizes["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert "eight" in sizes["deployment"].lower()
    assert sizes["timed_seed_why"] and sizes["check"]["why"]
    assert {"rotary_pairing", "mtp_loss_weight", "mtp_hidden",
            "mtp_concatenation", "optimizer_params", "init"} \
        <= set(sizes["assumed"])


def test_every_seed_gets_the_same_timed_work():
    c = manifest.resolve(CELL, rehearse=True)
    cfg = c.config_module
    a = cfg.make_pool(1, c.sizes, c.mix, 1, 2)
    b = cfg.make_pool(2 ** 31 + 5, c.sizes, c.mix, 1, 2)
    for (xa, ya), (xb, yb) in zip(a, b):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
        np.testing.assert_array_equal(xa[:, 1:], ya[:, :-1])  # next token
    xa, _ = cfg.check_batch(1, c.sizes, c.mix)
    xb, _ = cfg.check_batch(2 ** 31 + 5, c.sizes, c.mix)
    assert not np.array_equal(xa, xb)        # the check draws from --seed
    mx.random.seed(1)
    w1 = cfg.build(mx, c.sizes)["net"].collect_params()
    mx.random.seed(2)
    w2 = cfg.build(mx, c.sizes)["net"].collect_params()
    for name in w1:
        np.testing.assert_array_equal(w1[name].data().asnumpy(),
                                      w2[name].data().asnumpy())


# -- the cell's readers ---------------------------------------------------------
NEW_READERS = {m["name"]: m for m in manifest.load_benchmark()["per_layer"]
               if m.get("workloads") == [CELL]}


def test_rehearsal_of_a_traced_run_reports_the_accepted_counters(capsys):
    assert len(NEW_READERS) == 7
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 77),
                   "--seconds", "1", "--trace", "1", "--rehearse"],
                  t_start=time.perf_counter())
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    line = json.loads(out.out.strip().splitlines()[-1][len("REHEARSAL "):])
    assert line["correct"] is True, line["checks"]
    got = line["metrics"]
    assert got["step.dispatches_per_step"]["value"] == 1.0
    # the CPU has no device plane: this cell's readers, all of the device
    # trace, have nothing to read and the line leaves them out
    assert not set(NEW_READERS) & set(got)
    assert "expert layers" in out.err and "'moe.rows_overflow': 0.0" in out.err


def test_the_trace_readers_on_a_view_by_hand(monkeypatch):
    """Every new device-trace reader on a hand-made ``scope_view``: one step
    of 1 s (the rows below are its percents), rows named as the program's
    scopes name them."""
    lm, seq = "Glm4MoeLiteForCausalLM", "HybridSequential"
    layer = [lm, "Glm4MoeLiteModel", seq, "Glm4MoeLiteDecoderLayer"]
    mtp = [lm, "MTPModule"]
    mtp_layer = mtp + ["Glm4MoeLiteMTP", seq, "Glm4MoeLiteDecoderLayer"]
    attn, moe = layer + ["LatentAttention"], layer + ["Glm4MoeLiteMoE"]
    rows = [
        (attn + ["LatentDown", "Dense"], 3e-3),
        (attn + ["LatentDown", "RMSNorm"], 1e-3),
        (attn + ["LatentUp", "Dense"], 4e-3),
        (attn + ["LatentQK", "Rotary"], 1e-3), (attn + ["LatentQK"], 1e-3),
        (attn + ["LatentCore"], 30e-3),
        (attn + ["LatentOut", "Dense"], 5e-3),
        (layer + ["Glm4MoeLiteMLP", "Dense"], 6e-3),
        (moe + ["MoERouter"], 1e-3), (moe + ["MoEDispatch"], 2e-3),
        (moe + ["MoEExperts"], 5e-3), (moe + ["MoECombine"], 1e-3),
        (moe + ["Glm4MoeLiteMLP", "Dense"], 3e-3),
        (layer + ["RMSNorm"], 2e-3),
        ([lm, "Dense"], 7e-3),
        (mtp + ["Embedding"], 0.5e-3),
        (mtp + ["Glm4MoeLiteMTP", "MTPProjection", "Dense"], 1.5e-3),
        (mtp_layer + ["LatentAttention", "LatentCore"], 6e-3),
        (mtp_layer + ["LatentAttention", "LatentUp", "Dense"], 1e-3),
        (mtp_layer + ["Glm4MoeLiteMoE", "MoEExperts"], 1e-3),
        (mtp + ["Dense"], 7e-3),
        (["MultiTokenCrossEntropyLoss", "NextTokenLoss"], 2e-3),
        (["MultiTokenCrossEntropyLoss", "MultiTokenLoss"], 2e-3),
    ]
    view = {"steps": 1, "busy_s": 1.0,
            "rows": [{"pass": "forward", "classes": c, "step_scope": None,
                      "s": 10 * s} for c, s in rows]}
    monkeypatch.setattr(scope_view, "traced", lambda obs: view)
    c = manifest.resolve(CELL)
    obs = {"trace": {"steps": 1}, "batch": 1, "chips": 1,
           "peak": manifest.peak_for("TPU v5 lite"),
           "sizes": c.sizes, "mix": c.mix,
           "moe_traced": {"moe.rows_held": 5 * 4000.0 * 7,
                          "moe.steps": 5 * 7.0}}

    def read(name):
        return manifest.load_module("layer_metrics", name).read(obs)

    assert read("kernel.latent_core_share") == pytest.approx(36.0)
    # the mixer but its core and the output projection, the module's too
    assert read("kernel.latent_proj_share") == pytest.approx(11.0)
    assert read("kernel.gated_expert_share") == pytest.approx(13.0)
    assert read("kernel.mtp_share") == pytest.approx(19.0)
    assert read("kernel.dual_head_share") == pytest.approx(18.0)
    # six cores at 8,192 keys, three passes
    core_ops = 6 * 6 * 8192 * 20 * 512 * 4096
    assert read("latent_core_roofline") == pytest.approx(
        100 * core_ops / 197e12 / 0.36)
    # 4,000 rows a layer a step, five layers, three products, three passes
    expert_ops = 6 * 5 * 4000 * 9_437_184
    assert read("gated_expert_roofline") == pytest.approx(
        100 * expert_ops / 197e12 / 0.06)
    for name in NEW_READERS:
        assert 0 <= read(name) <= 100, name
    # the rooflines count from the obs they are given, and a driver that
    # states no sizes gives them nothing to read
    for name in ("latent_core_roofline", "gated_expert_roofline"):
        bare = {k: v for k, v in obs.items() if k != "sizes"}
        assert manifest.load_module("layer_metrics", name).read(bare) is None
    # a program without the scopes (the parent), or a run without a device
    # trace: nothing to read, nothing raised
    monkeypatch.setattr(scope_view, "traced", lambda obs: None)
    for name in NEW_READERS:
        assert read(name) is None, name
