"""Configuration ``nemotron_twotower_30b_ep16``: its plain reference against
the Gluon forward at toy widths on the CPU, each named term of the
mathematics against the configuration's own tolerances, the operation
counts against hand counts, the size of the cut, and the readers this
configuration's cell brings."""
import json
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from perfbench import manifest, opcount, run, scope_view

CELL = "nemotron_h_train_s8k"
DRIVER = manifest.load_module("drivers", "train_fixed_shape")


@pytest.fixture(scope="module")
def pair():
    """The program's float32 forward (no AMP) and everything the reference
    needs, at the rehearsal's widths with the check's own weights, so that
    every bias and scale is a term that shows."""
    c = manifest.resolve(CELL, rehearse=True)
    # the cell's own pattern, and projections into the residual stream that
    # start five times larger: at 64 wide a layer's output is otherwise small
    # beside the embedding, where at 2,688 it is several times the larger
    cfg, sizes = c.config_module, {
        **c.sizes, "hybrid_override_pattern": "MEMEM*EME",
        "num_hidden_layers": 9, "rescale_layers": 0.02}
    net = cfg.build(mx, sizes)["net"]
    x, y = cfg.check_batch(5, sizes, {"seq_len": 48})
    with mx.autograd.predict_mode():
        net(mx.nd.array(x))
    DRIVER._check_weights(net, sizes["check"], 5)
    with mx.autograd.predict_mode():
        got = net(mx.nd.array(x))._data
    params = {n: p.data().asnumpy() for n, p in net.collect_params().items()}
    return cfg, sizes, params, x, y, got


def _compare(pair, drop=None, operand_dtype=None, **changed):
    import jax
    import jax.numpy as jnp

    cfg, sizes, params, x, y, got = pair
    if drop:
        assert any(n.endswith(drop) for n in params), drop
        params = {n: v * (0 if n.endswith(drop) else 1)
                  for n, v in params.items()}
    sizes = {**sizes, **changed}
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits, margins = cfg.reference_parts(
            params, x, y, sizes, operand_dtype=operand_dtype)
    logp = jax.nn.log_softmax(got.astype(jnp.float32), -1)
    loss = float(-jnp.take_along_axis(logp, jnp.asarray(y)[..., None],
                                      -1).mean())
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


def test_eight_bit_operands_fail_and_bf16_operands_pass(pair):
    """The nearest precision below the configuration's: both operands of
    every product rounded to float8 (e4m3) read far over the median's
    limit; rounded to bf16, the configuration's own precision, well
    under it."""
    import jax.numpy as jnp

    low = _compare(pair, operand_dtype=jnp.float8_e4m3fn)
    assert not low["ok"] and low["logits_err"] > 2 * low["logits_tol"], low
    own = _compare(pair, operand_dtype=jnp.bfloat16)
    assert own["logits_err"] < 0.5 * own["logits_tol"], own


def test_reference_signature_is_the_harnesses(pair):
    cfg, sizes, params, x, y, _ = pair
    loss, logits = cfg.reference(params, x, y, sizes)
    assert logits.shape == x.shape + (sizes["vocab_size"],)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("drop", [
    "mixer.D",                            # the scan's D x
    "mixer.dt_bias",                      # softplus(dt + dt_bias)
    "mixer.conv_bias",                    # the convolution's bias
    "shared_expert.down_proj.weight",     # the shared expert
    "e_score_correction_bias",            # the selection's + b
    "mixer.experts_down",                 # the routed experts held here
    "mixer.o_proj.weight",                # the attention layer
])
def test_a_missing_term_fails_the_configurations_tolerance(pair, drop):
    out = _compare(pair, drop=drop)
    assert not out["ok"], (drop, out)


def test_a_missing_scaling_factor_fails_the_configurations_tolerance(pair):
    assert not _compare(pair, routed_scaling_factor=1.0)["ok"]


def test_a_position_is_exposed_by_the_references_margin_alone(pair):
    """``compare`` takes the exposed positions from the reference's margins,
    never from what the program chose: with a margin of 1 everything is
    exposed, and the share bound refuses the check."""
    import jax.numpy as jnp

    cfg, sizes, params, x, y, got = pair
    ref_loss, ref_logits, margins = cfg.reference_parts(params, x, y, sizes)
    assert margins.shape == (sizes["hybrid_override_pattern"].count("E"),) \
        + x.shape and float(jnp.min(margins)) >= 0
    spec = {**manifest.resolve(CELL).sizes["check"], "tie_margin": 1.0}
    out = cfg.compare(got, float(ref_loss), float(ref_loss), ref_logits,
                      margins, {**sizes, "check": spec})
    assert out["tied_share"] == 1.0 == out["exposed_share"] and out["ok"]
    # an outlier at a position the reference does not expose counts against
    # the unexposed limit, whatever the program did there
    spec = {**spec, "tie_margin": 0.0}
    moved = got.at[0, :3].add(1.0)        # under half of a block of 8
    out = cfg.compare(moved, float(ref_loss), float(ref_loss), ref_logits,
                      margins, {**sizes, "check": spec})
    assert out["exposed_share"] == 0.0 and not out["ok"]
    assert out["unexposed_outlier_share"] == pytest.approx(3 / x.size)
    assert out["logits_err"] <= out["logits_tol"]     # not by the median


@pytest.mark.parametrize("held, margin", [
    ((0,), 0.2),            # chosen: 0.9 from the first unchosen 0.7
    ((1,), 0.1),            # the last chosen, 0.8, from 0.7
    ((2,), 0.1),            # the first unchosen, 0.7, from 0.8
    ((4,), 0.3),            # far out: 0.5 from 0.8
    ((0, 4), 0.2),          # the nearer of the two
    ((3, 4), 0.2),          # a swap of 1 and 2, held elsewhere, is no tie
])
def test_a_margin_is_a_held_experts_distance_from_changing_sides(held,
                                                                 margin):
    import jax.numpy as jnp

    cfg = manifest.resolve(CELL).config_module
    biased = jnp.asarray([[0.7, 0.5, 0.9, 0.6, 0.8]])[:, [2, 4, 0, 3, 1]]
    assert float(cfg.held_margin(biased, 2, held)[0]) \
        == pytest.approx(margin)


def _by_hand(errors, margins):
    """``compare`` at the cell's own sizes and limits on hand-made
    per-position errors (one logit a position, the reference's scale 1) and
    margins of one expert layer."""
    import jax.numpy as jnp

    c = manifest.resolve(CELL)
    errors = jnp.asarray(errors, jnp.float32)
    ref = jnp.zeros((1, errors.size, 2)).at[0, 0, 1].set(1.0)
    got = ref.at[0, :, 0].add(errors)
    return c.config_module.compare(
        got, 1.0, 1.0, ref, jnp.asarray(margins, jnp.float32)[None, None],
        c.sizes)


def test_a_tie_exposes_itself_and_the_convolutions_reach_after_it():
    spec = manifest.resolve(CELL).sizes["check"]
    margins = np.ones(512)
    margins[[5, 300]] = 0.5 * spec["tie_margin"]
    errors = np.full(512, 0.01)
    errors[[5, 6, 7, 8, 300, 303]] = 0.1          # a flip and what it reaches
    out = _by_hand(errors, margins)
    assert out["tied_share"] == 2 / 512 and out["exposed_share"] == 8 / 512
    assert out["exposed_outlier_share"] == 6 / 8
    assert out["unexposed_outlier_share"] == 0
    errors[[4, 9]] = 0.1                          # before it, and beyond
    assert _by_hand(errors, margins)["unexposed_outlier_share"] \
        == pytest.approx(2 / 504)


@pytest.mark.parametrize("wrong", [
    range(384, 512),        # the scan's last chunk of a sequence of 512
    range(128, 256),        # a block of attention keys and queries
    range(200, 330),        # a stretch that fills no block
])
def test_a_wrong_stretch_of_positions_fails_by_its_blocks_median(wrong):
    """A fault local to a stretch of the sequence moves neither the
    sequence's median nor, here, the outlier shares (every position is
    exposed): its block's median refuses it."""
    errors = np.full(512, 0.009)
    assert _by_hand(errors, np.zeros(512))["ok"]
    errors[list(wrong)] = 0.029
    out = _by_hand(errors, np.zeros(512))
    assert not out["ok"] and out["logits_err"] == pytest.approx(0.029)
    assert out["logits_err_median"] == pytest.approx(0.009)
    assert out["exposed_outlier_share"] == 0 == out["unexposed_outlier_share"]
    # a sequence that fills no whole block is one block
    assert _by_hand(errors[:100], np.zeros(100))["block"] == 128


# -- operation counts against hand counts, at the published widths ------------
def test_one_layer_of_each_kind_by_hand():
    c = manifest.resolve(CELL)
    cfg, s = c.config_module, c.sizes
    # a token through a Mamba-2 mixer: in_proj 2688 x 10304, the depthwise
    # convolution 6144 x 4, the scan's four products (8 x 128 x 128 +
    # 3 x 64 x 128 x 64), out_proj 4096 x 2688
    assert cfg.scan_macs(s, 1) == 131_072 + 3 * 524_288 == 1_703_936
    assert cfg.mamba_macs(s, 1) == 27_697_152 + 24_576 + 1_703_936 \
        + 11_010_048 == 40_435_712
    # attention at 8,192: q and o 2688 x 4096 each, k and v 2688 x 256 each,
    # the causal core 32 heads x 8192 keys x 128 a token (half the square)
    assert cfg.attention_core_macs(s, 8192) == 8192 * 33_554_432
    assert cfg.attention_macs(s, 8192) == 8192 * (
        2 * 11_010_048 + 2 * 688_128 + 33_554_432) == 8192 * 56_950_784
    # experts: router 2688 x 128, shared 2 x 2688 x 3712, and the held
    # experts at the MEAN share: 6 x 8/128 rows a token x 2 x 2688 x 1856
    assert cfg.expert_row_macs(s) == 9_977_856
    assert cfg.mean_held_rows(s, 8192) == 3072
    assert cfg.moe_macs(s, 8192) == 8192 * (344_064 + 19_955_712) \
        + 3072 * 9_977_856 == 8192 * 24_041_472
    # the whole cut, with the head's 2688 x 16384
    per_token = 4 * 40_435_712 + 56_950_784 + 4 * 24_041_472 + 44_040_192
    assert per_token == 358_899_712
    assert cfg.forward_macs(s, 8192) == 8192 * per_token
    assert cfg.ops_per_sample(s, c.mix) == opcount.train_ops(8192 * per_token)


def test_the_cut_has_667_million_parameters_without_allocating_them():
    c = manifest.resolve(CELL)
    sizes = c.sizes
    net = mx.gluon.model_zoo.nemotron_h.nemotron_h(
        {**sizes, "n_routed_experts": sizes["router_experts"]},
        held_experts=range(sizes["n_routed_experts"]))
    params = net.collect_params()
    assert all(p._data is None for p in params.values())   # never initialised
    total = sum(int(np.prod(p.shape)) for p in params.values())
    assert abs(total - 667e6) < 0.01 * 667e6, total
    by_kind = {k: sum(int(np.prod(p.shape)) for n, p in params.items()
                      if n.startswith(f"backbone.layers.{i}."))
               for k, i in (("M", 0), ("E", 1), ("*", 5))}
    # the issue's arithmetic: 38.7 M, 20.3 M + 8 x 9.98 M, 23.4 M
    assert round(by_kind["M"] / 1e6, 1) == 38.7
    assert round(by_kind["*"] / 1e6, 1) == 23.4
    assert round(by_kind["E"] / 1e6, 1) == round(20.3 + 8 * 9.98, 1)


def test_the_file_states_the_deployment_and_the_catalogs_numbers():
    sizes = manifest.resolve(CELL).sizes
    row = None
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            row = next(json.loads(l) for l in f if sizes["source"] in l)
    except OSError:
        pytest.skip("the catalog is not on this machine")
    for key, value in row["config"].items():
        if key in sizes["reduced"] or key == "hybrid_override_pattern":
            assert sizes["published"][key] == value
        else:
            assert sizes[key] == value, key
    assert row["config"]["hybrid_override_pattern"].startswith(
        sizes["hybrid_override_pattern"])
    assert sizes["router_experts"] == row["config"]["n_routed_experts"]
    assert len(sizes["hybrid_override_pattern"]) == sizes["num_hidden_layers"]
    assert "sixteen" in sizes["deployment"].lower()
    assert sizes["timed_seed_why"] and sizes["check"]["why"]


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


def test_rehearsal_of_a_traced_run_finds_the_counters_a_number(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 77),
                   "--seconds", "1", "--trace", "1", "--rehearse"],
                  t_start=time.perf_counter())
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    line = json.loads(out.out.strip().splitlines()[-1][len("REHEARSAL "):])
    assert line["correct"] is True, line["checks"]
    got = line["metrics"]
    assert got["step.dispatches_per_step"]["value"] == 1.0
    assert got["moe.rows_overflow"]["value"] == 0
    assert 0 < got["moe.rows_held_share"]["value"] < 4
    assert got["moe.load_max_over_mean"]["value"] >= 1
    # the CPU has no device plane: the trace's readers have nothing to read
    missing = set(NEW_READERS) - set(got)
    assert missing == {n for n, m in NEW_READERS.items()
                       if m["source"] == "device_trace"}


def test_the_trace_readers_on_a_view_by_hand(monkeypatch):
    """Every new device-trace reader on a hand-made ``scope_view``: one step
    of 100 ms, rows named as the program's scopes name them."""
    lm, mdl, blk = "NemotronHForCausalLM", "NemotronHModel", "NemotronHBlock"
    seq, stack = "HybridSequential", [lm, mdl]
    mixer = stack + [seq, blk, "NemotronHMamba2Mixer"]
    moe = stack + [seq, blk, "NemotronHMoE"]
    rows = [
        (mixer + ["Dense"], 20e-3), (mixer + ["SsdScan"], 15e-3),
        (mixer + ["CausalConv1d"], 5e-3),
        (moe + ["MoERouter"], 1e-3), (moe + ["MoEDispatch"], 2e-3),
        (moe + ["MoEExperts"], 4e-3), (moe + ["MoECombine"], 1e-3),
        (moe + ["NemotronHMLP", "Dense"], 12e-3),
        (stack + [seq, blk, "NemotronHAttention"], 10e-3),
        (stack + [seq, blk, "NemotronHAttention", "Dense"], 8e-3),
        ([lm, "Dense"], 14e-3), (["SoftmaxCrossEntropyLoss"], 2e-3),
        (stack + [seq, blk, "RMSNorm"], 6e-3),
    ]
    view = {"steps": 1, "busy_s": 0.1,
            "rows": [{"pass": "forward", "classes": c, "step_scope": None,
                      "s": s} for c, s in rows]}
    monkeypatch.setattr(scope_view, "traced", lambda obs: view)
    c = manifest.resolve(CELL)
    # what driver train_fixed_shape_routed adds to obs: the cell's sizes and
    # mix, and the expert layers' counts over the traced steps alone
    obs = {"trace": {"steps": 1}, "batch": 1, "chips": 1,
           "peak": manifest.peak_for("TPU v5 lite"),
           "sizes": c.sizes, "mix": c.mix,
           "moe_traced": {"moe.rows_held": 4 * 3000.0 * 7,
                          "moe.steps": 4 * 7.0}}

    def read(name):
        return manifest.load_module("layer_metrics", name).read(obs)

    assert read("kernel.ssm_mixer_share") == pytest.approx(40.0)
    assert read("kernel.ssd_scan_share") == pytest.approx(15.0)
    assert read("kernel.expert_share") == pytest.approx(20.0)
    assert read("kernel.expert_dispatch_share") == pytest.approx(4.0)
    assert read("kernel.causal_attention_core_share") == pytest.approx(10.0)
    assert read("kernel.lm_head_share") == pytest.approx(16.0)
    scan_ops = 6 * 4 * c.config_module.scan_macs(c.sizes, 8192)
    assert read("ssd_scan_roofline") == pytest.approx(
        100 * scan_ops / 197e12 / 15e-3)
    # 3,000 rows a layer a step, four layers, two products, three passes
    expert_ops = 6 * 4 * 3000 * 9_977_856
    assert read("expert_matmul_roofline") == pytest.approx(
        100 * expert_ops / 197e12 / 4e-3)
    for name in NEW_READERS:
        value = read(name) if NEW_READERS[name]["source"] == "device_trace" \
            else 0.0
        assert value is not None and 0 <= value <= 100, name
    # the rooflines count from the obs they are given, not from a cell's
    # name: another mix on this configuration reads at its own length, and
    # a driver that states no sizes gives them nothing to read
    longer = {**obs, "mix": {**c.mix, "seq_len": 32768}}
    assert manifest.load_module("layer_metrics", "ssd_scan_roofline").read(
        longer) == pytest.approx(4 * 100 * scan_ops / 197e12 / 15e-3)
    for name in ("ssd_scan_roofline", "expert_matmul_roofline"):
        bare = {k: v for k, v in obs.items() if k != "sizes"}
        assert manifest.load_module("layer_metrics", name).read(bare) is None
    # a program without the scopes (the parent of the PR that added them),
    # or a run without a device trace: nothing to read, nothing raised
    monkeypatch.setattr(scope_view, "traced", lambda obs: None)
    for name, m in NEW_READERS.items():
        if m["source"] == "device_trace":
            assert read(name) is None, name


# -- the two drivers that run train_fixed_shape with a part of their own ------
def _stale_base(monkeypatch, result):
    """``train_fixed_shape`` as it would be after a rename there: it takes
    no notice of what a driver assigns to it, and reports ``result``."""
    from types import SimpleNamespace

    real = manifest.load_module
    monkeypatch.setattr(
        manifest, "load_module", lambda directory, name:
        SimpleNamespace(run=lambda *a: result, _traced=None, _steps=None)
        if (directory, name) == ("drivers", "train_fixed_shape")
        else real(directory, name))


def _result(reference_check, dispatches, steps):
    return {"checks": {"reference_check": reference_check},
            "end_to_end": {"samples_per_s": 1.0, "mfu": 1.0},
            "obs": {"counters": {"dispatches": dispatches}, "steps": steps,
                    "batch": 32, "ops_per_step": 1, "memory": []}}


@pytest.mark.parametrize("workload, result", [
    # the accepted comparison answered, not the configuration's
    (CELL, _result({"ok": True, "logits_err": 0.0}, 20, 20)),
    # one dispatch a window: the accepted loop ran, not the accumulation's
    ("bert_base_train_accum4", _result({"ok": True}, 20, 20)),
])
def test_a_hook_the_accepted_driver_ignores_fails_loudly(monkeypatch,
                                                         workload, result):
    from types import SimpleNamespace

    cell = manifest.resolve(workload, rehearse=True)
    _stale_base(monkeypatch, result)
    opts = SimpleNamespace(trace=False, out_dir=".", seed=1)
    with pytest.raises(RuntimeError, match="renamed"):
        cell.driver.run(cell, opts, [], {}, lambda text: None)
