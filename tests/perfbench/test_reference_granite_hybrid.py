"""Configuration ``granite_4_0_h_micro_tp2``: its plain reference against the
Gluon forward at toy widths on the CPU, each named term of the mathematics
(every multiplier among them) and float8 operands against the
configuration's own tolerances, the operation counts against hand counts,
the size of the cut, and the readers this configuration's cell brings."""
import inspect
import json
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from perfbench import manifest, opcount, run, scope_view

CELL = "granite_h_micro_train_s8k"
CONFIG = "granite_4_0_h_micro_tp2"
DRIVER = manifest.load_module("drivers", "train_fixed_shape")


@pytest.fixture(scope="module")
def pair():
    """The program's float32 forward (no AMP) and everything the reference
    needs, at the rehearsal's widths with the check's own weights, so that
    every norm's scale is a term that shows."""
    c = manifest.resolve(CELL, rehearse=True)
    cfg, sizes = c.config_module, c.sizes
    mx.random.seed(5)
    built = cfg.build(mx, sizes)
    net = built["net"]
    x, y = cfg.check_batch(5, sizes, {"seq_len": 48})
    with mx.autograd.predict_mode():
        net(mx.nd.array(x))
    # every matrix sqrt(2,048 / 64) times the configuration's normal of
    # 0.02, so that a projection's output is as large over 64 features as
    # over the cell's 2,048 (the cell's own readings: PERF.md section 2)
    gain = (manifest.resolve(CELL).sizes["hidden_size"]
            / sizes["hidden_size"]) ** 0.5
    for name, p in net.collect_params().items():
        if name.endswith(".weight"):
            p.set_data(p.data().asnumpy() * gain)
    DRIVER._check_weights(net, sizes["check"], 5)
    with mx.autograd.predict_mode():
        got = net(mx.nd.array(x))
        loss = float(built["head_loss"](got, mx.nd.array(y)).asnumpy())
    params = {n: p.data().asnumpy() for n, p in net.collect_params().items()}
    return cfg, sizes, params, x, y, got.asnumpy(), loss


def _compare(pair, **kw):
    """``train_fixed_shape``'s comparison at the CELL's own limits: the
    largest distance of a logit from the reference's over the reference's
    largest logit, and the loss's relative error."""
    import jax

    cfg, sizes, params, x, y, got, loss = pair
    with jax.default_matmul_precision("highest"):
        ref_loss, ref = cfg.reference(params, x, y, sizes, **kw)
    ref, ref_loss = np.asarray(ref), float(ref_loss)
    spec = manifest.resolve(CELL).sizes["check"]     # the cell's own limits
    logits_err = float(np.abs(got - ref).max() / np.abs(ref).max())
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    return {"ok": logits_err <= spec["logits_tol"]
            and loss_err <= spec["loss_tol"],
            "logits_err": logits_err, "logits_tol": spec["logits_tol"],
            "loss_err": loss_err, "loss_tol": spec["loss_tol"]}


def test_reference_equals_the_gluon_forward(pair):
    out = _compare(pair)
    # float32 against float32, chunks against a state a token: rounding
    assert out["ok"] and out["logits_err"] < 2e-5 and out["loss_err"] < 1e-6


def test_eight_bit_operands_fail_and_bf16_operands_pass(pair):
    """The nearest precision below the configuration's: both operands of
    every product rounded to float8 (e4m3) read over the limit, rounded to
    bf16, the configuration's own precision, well under it (at the cell's
    size on the chip: PERF.md section 2)."""
    import jax.numpy as jnp

    low = _compare(pair, operand_dtype=jnp.float8_e4m3fn)
    assert not low["ok"] and low["logits_err"] > 1.1 * low["logits_tol"], low
    own = _compare(pair, operand_dtype=jnp.bfloat16)
    assert own["ok"] and own["logits_err"] < 0.25 * own["logits_tol"], own


def test_reference_signature_is_the_harnesses_and_it_imports_no_operator(
        pair):
    cfg, sizes, params, x, y, _, _ = pair
    loss, logits = cfg.reference(params, x, y, sizes)
    assert logits.shape == x.shape + (sizes["vocab_size"],)
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError, match="without="):
        cfg.reference(params, x, y, sizes, without=("rope",))
    with open(cfg.__file__) as f:
        text = f.read()
    assert "import mxnet_tpu" not in text and "from mxnet_tpu" not in text
    assert "mx." not in inspect.getsource(cfg.reference)


@pytest.mark.parametrize("term", [
    "embedding_multiplier",   # 12 x E[ids]
    "residual_multiplier",    # 0.22 x every sub-block's output
    "logits_scaling",         # the logits / 8
    "attention_multiplier",   # 1/64 on the scores, not 1/sqrt(64)
    "decay",                  # exp(dt A) on the state
    "skip",                   # D x
    "convolution",            # the depthwise causal convolution
    "conv_bias",              # its bias
    "output_gate",            # silu(z) on the scan's output
    "gated_norm",             # the RMS norm over the group
])
def test_a_missing_term_fails_the_configurations_tolerance(pair, term):
    assert term in pair[0].TERMS
    out = _compare(pair, without=(term,))
    assert not out["ok"], (term, out)


def test_every_term_has_its_case():
    want = set(manifest.load_module("configs", CONFIG).TERMS)
    marks = test_a_missing_term_fails_the_configurations_tolerance.pytestmark
    assert set(marks[0].args[1]) == want


# -- operation counts against hand counts, at the published widths ------------
def test_one_layer_of_each_kind_by_hand():
    c = manifest.resolve(CELL)
    cfg, s = c.config_module, c.sizes
    # a token through a Mamba-2 layer's held projections: in_proj 2048 x
    # (2048 z + 2048 x + 128 B + 128 C + 32 dt), out_proj 2048 x 2048
    assert cfg.mamba_proj_macs(s, 1) == 2048 * 4384 + 2048 * 2048 \
        == 13_172_736
    # the scan at chunks of 256: C B^T 256 x 128 (one group), the masked
    # product 32 heads x 256 x 64, the chunk's state and C S 32 x 128 x 64
    assert cfg.scan_macs(s, 1) == 256 * 128 + 32 * 256 * 64 \
        + 2 * 32 * 128 * 64 == 1_081_344
    # the feed-forward's three products of 2048 x 8192, in every layer
    assert cfg.ffn_macs(s, 1) == 3 * 2048 * 8192 == 50_331_648
    # the attention layer: q and o 2048 x 1024, k and v 2048 x 256; the
    # core 16 heads x (64 + 64) x 8192 keys at half the square
    assert cfg.attention_proj_macs(s, 1) == 5_242_880
    assert cfg.attention_core_macs(s, 8192) == 8192 * 16 * 128 * 4096 \
        == 8192 * 8_388_608
    # the tied head, 2048 x 12544
    assert cfg.head_macs(s, 1) == 25_690_112
    per_token = 9 * (13_172_736 + 1_081_344 + 50_331_648) \
        + (5_242_880 + 8_388_608 + 50_331_648) + 25_690_112
    assert per_token == 670_924_800
    assert cfg.forward_macs(s, 8192) == 8192 * per_token
    assert cfg.ops_per_sample(s, c.mix) == opcount.train_ops(8192 * per_token)
    # about 33 TFLOP a trained sequence
    assert round(cfg.ops_per_sample(s, c.mix) / 1e12, 2) == 32.98


def test_the_cut_has_652970080_parameters_without_allocating_them():
    sizes = manifest.resolve(CELL).sizes
    net = mx.gluon.model_zoo.granite_hybrid.granite_hybrid(
        {**sizes, **{k: sizes["published"][k] for k in (
            "mamba_n_heads", "num_attention_heads", "num_key_value_heads")}},
        held_heads=range(sizes["mamba_n_heads"]))
    params = net.collect_params()
    assert all(p._data is None for p in params.values())   # never initialised

    def count(prefix):
        return sum(int(np.prod(p.shape)) for n, p in params.items()
                   if n.startswith(prefix))

    # a Mamba-2 mixer's share: in_proj 4,384 x 2,048, the convolution's
    # weights and bias over 2,304 channels, dt_bias, A_log and D a head,
    # the norm's 2,048, out_proj 2,048 x 2,048
    assert count("model.layers.0.mixer.") == 8_978_432 + 4 * 2_304 + 2_304 \
        + 3 * 32 + 2_048 + 4_194_304 == 13_186_400
    assert count("model.layers.0.mlp.") == 50_331_648
    for i in (0, 1, 2, 3, 4, 6, 7, 8, 9):      # with the two norms
        assert count(f"model.layers.{i}.") == 63_522_144
    assert count("model.layers.5.mixer.") == 5_242_880
    assert count("model.layers.5.") == 55_578_624
    assert count("model.embed_tokens.") == 25_690_112     # and the head
    assert count("model.norm.") == 2_048
    assert count("") == 652_970_080
    # 16 bytes a parameter: a float32 weight, its gradient, Adam's m and v
    assert round(16 * count("") / 2 ** 30, 2) == 9.73
    assert params["model.layers.0.mixer.in_proj.weight"].shape == (4384, 2048)
    assert params["model.layers.5.mixer.k_proj.weight"].shape == (256, 2048)
    assert not [n for n in params if "lm_head" in n]


def test_the_file_states_the_deployment_and_the_catalogs_numbers():
    sizes = manifest.resolve(CELL).sizes
    row = None
    catalog = os.environ.get("MODEL_CATALOG_JSONL")
    if not catalog:
        pytest.skip("MODEL_CATALOG_JSONL names no catalog of architectures")
    try:
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if sizes["source"] in l)
    except OSError:
        pytest.skip("the catalog is not on this machine")
    for key, value in row["config"].items():
        if key in sizes["reduced"]:
            assert sizes["published"][key] == value
        else:
            assert sizes[key] == value, key
    assert sizes["reduced"] == [
        "num_hidden_layers", "mamba_n_heads", "num_attention_heads",
        "num_key_value_heads", "vocab_size"]
    for key in ("mamba_n_heads", "num_attention_heads",
                "num_key_value_heads"):
        assert 2 * sizes[key] == sizes["published"][key]
    assert sizes["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert sizes["layer_types"][:10] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    assert "two chips" in sizes["deployment"].lower()
    assert sizes["check"]["why"] and sizes["loss_fall_why"]
    assert {"init", "A_log_dt_bias", "time_step_limit", "feed_forward",
            "optimizer_params"} <= set(sizes["assumed"])
    assert (sizes["recompute_layers"], sizes["amp_dtype"]) == (True,
                                                              "bfloat16")
    mix = manifest.resolve(CELL).mix
    assert (mix["seq_len"], mix["batch_candidates"]) == (8192, [2, 1])
    assert mix["batch_per_chip"] in (1, 2) and mix["sizing"]["step_gib"]
    assert mix["env"] == {"MXNET_SPMD_MESH": "off"}


def test_the_seed_draws_the_weights_the_pool_and_the_check():
    c = manifest.resolve(CELL, rehearse=True)
    cfg = c.config_module
    (xa, ya), = cfg.make_pool(1, c.sizes, c.mix, 1, 1)
    (xb, _), = cfg.make_pool(2 ** 31 + 5, c.sizes, c.mix, 1, 1)
    np.testing.assert_array_equal(xa[:, 1:], ya[:, :-1])     # next token
    assert not np.array_equal(xa, xb)
    assert xa.max() < c.sizes["vocab_size"] and xa.dtype == np.int32
    xc, _ = cfg.check_batch(1, c.sizes, c.mix)
    assert not np.array_equal(xa, xc)


# -- the driver and the cell's readers ----------------------------------------
NEW_READERS = ("scan_roofline", "causal_core_roofline",
               "kernel.tied_head_share")
SHARED_READERS = ("kernel.ssm_mixer_share", "kernel.ssd_scan_share",
                  "kernel.causal_attention_core_share")


def test_rehearsal_of_a_traced_run_goes_through_the_seq_driver(capsys):
    """A fraction of a sequence is run as one sequence, the comparison is
    ``train_fixed_shape``'s own, and the CPU has no device plane: this
    cell's readers, all of the device trace, leave the line alone."""
    assert manifest.resolve(CELL).mix["kind"] == "train_fixed_shape_seq"
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 91),
                   "--seconds", "1", "--trace", "1", "--rehearse"],
                  t_start=time.perf_counter())
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    line = json.loads(out.out.strip().splitlines()[-1][len("REHEARSAL "):])
    assert line["correct"] is True, line["checks"]
    got = line["metrics"]
    assert got["step.dispatches_per_step"]["value"] == 1.0
    assert not set(NEW_READERS) & set(got)
    assert "steps of 1 in" in out.err and "memory by device" in out.err


def test_the_trace_readers_on_a_view_by_hand(monkeypatch):
    """Every new device-trace reader, and the accepted ones this cell shares
    by its classes, on a hand-made ``scope_view``: one step of 1 s (the rows
    below are its percents), rows named as the program's scopes name them."""
    lm, seq = "GraniteHybridForCausalLM", "HybridSequential"
    layer = [lm, "GraniteHybridModel", seq, "GraniteHybridDecoderLayer"]
    mamba, attention = layer + ["NemotronHMamba2Mixer"], \
        layer + ["NemotronHAttention"]
    rows = [
        (mamba + ["Dense"], 10e-3), (mamba + ["CausalConv1d"], 3e-3),
        (mamba + ["SsdScan"], 6e-3), (mamba, 2e-3),
        (attention + ["Dense"], 2e-3), (attention, 4e-3),
        (layer + ["GraniteHybridMLP", "Dense"], 60e-3),
        (layer + ["RMSNorm"], 1e-3),
        (layer[:2] + ["Embedding"], 1e-3),
        ([lm], 8e-3),
        (["SoftmaxCrossEntropyLoss"], 1e-3),
        ([lm, "GraniteHybridModel", "RMSNorm"], 2e-3),
    ]
    view = {"steps": 1, "busy_s": 1.0,
            "rows": [{"pass": "forward", "classes": c, "step_scope": None,
                      "s": 10 * s} for c, s in rows]}
    monkeypatch.setattr(scope_view, "traced", lambda obs: view)
    c = manifest.resolve(CELL)
    obs = {"trace": {"steps": 1}, "batch": 1, "chips": 1,
           "peak": manifest.peak_for("TPU v5 lite"),
           "sizes": c.sizes, "mix": c.mix}

    def read(name):
        return manifest.load_module("layer_metrics", name).read(obs)

    assert read("kernel.tied_head_share") == pytest.approx(9.0)
    # nine layers' chunk products at 8,192 tokens, three passes, over 0.06 s
    scan_ops = 6 * 9 * 8192 * 1_081_344
    assert read("scan_roofline") == pytest.approx(
        100 * scan_ops / 197e12 / 0.06)
    # one core at half its square, three passes, over the block itself
    core_ops = 6 * 8192 * 8_388_608
    assert read("causal_core_roofline") == pytest.approx(
        100 * core_ops / 197e12 / 0.04)
    # the accepted readers read this cell through the shared classes
    assert read("kernel.ssm_mixer_share") == pytest.approx(21.0)
    assert read("kernel.ssd_scan_share") == pytest.approx(6.0)
    assert read("kernel.causal_attention_core_share") == pytest.approx(4.0)
    for name in NEW_READERS + SHARED_READERS:
        assert 0 <= read(name) <= 100, name
    # the rooflines count from the obs they are given: a driver that states
    # no sizes gives them nothing to read
    bare = {k: v for k, v in obs.items() if k != "sizes"}
    for name in ("scan_roofline", "causal_core_roofline"):
        assert manifest.load_module("layer_metrics", name).read(bare) is None
    # the same readers count the state-space cell's own layers
    nemotron = manifest.resolve("nemotron_h_train_s8k")
    other = {**obs, "sizes": nemotron.sizes, "mix": nemotron.mix}
    assert manifest.load_module("layer_metrics", "scan_roofline").read(
        other) == pytest.approx(100 * 6 * 4 * 8192 * nemotron.config_module
                                .scan_macs(nemotron.sizes, 1) / 197e12 / 0.06)
    # a run without a device trace: nothing to read, nothing raised
    monkeypatch.setattr(scope_view, "traced", lambda obs: None)
    for name in NEW_READERS:
        assert read(name) is None, name


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_is_in_place_without_its_entry(name):
    """Each reader is in place, and so is its ``per_layer`` entry, appended
    at the end of the list: a device-trace share of the kernels layer that
    moves ``mfu`` in this cell (the two rooflines in the state-space cell
    too, whose scan and core they count from its own configuration)."""
    assert callable(manifest.load_module("layer_metrics", name).read)
    entry = next(m for m in manifest.load_benchmark()["per_layer"]
                 if m["name"] == name)
    assert (entry["unit"], entry["source"], entry["layer"], entry["moves"]) \
        == ("%", "device_trace", "kernels", "mfu")
    roofline = name.endswith("_roofline")
    assert entry["better"] == ("higher" if roofline else "lower")
    assert entry["workloads"] == (["nemotron_h_train_s8k", CELL] if roofline
                                  else [CELL])


@pytest.mark.parametrize("name", SHARED_READERS)
def test_a_shared_reader_names_this_cell(name):
    """The accepted readers that read this cell through the shared classes
    list it beside the state-space cell."""
    assert name in {m["name"] for m in manifest.resolve(CELL).per_layer}
    assert name in {m["name"] for m in
                    manifest.resolve("nemotron_h_train_s8k").per_layer}
