"""Configuration ``olmo_hybrid_7b_tp2``: its plain reference against the Gluon
forward at toy widths on the CPU, each named term of the mathematics and
float8 operands against the configuration's own tolerances, the operation
counts against hand counts, the size of the cut, the driver's two
differences, and the readers this configuration's cell brings."""
import inspect
import json
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from perfbench import manifest, opcount, run, scope_view

CELL = "olmo_hybrid_train_s8k"
DRIVER = manifest.load_module("drivers", "train_fixed_shape")


@pytest.fixture(scope="module")
def pair():
    """The program's float32 forward (no AMP) and everything the reference
    needs, at the rehearsal's widths with the check's own weights, so that
    every norm's scale is a term that shows."""
    c = manifest.resolve(CELL, rehearse=True)
    # weights six times the rehearsal's: at 64 features the projections are
    # otherwise so small that a softmax hardly tells normalised queries and
    # keys from raw ones, where at 3,840 it does
    cfg, sizes = c.config_module, {**c.sizes, "init_std": 0.12}
    mx.random.seed(5)
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
    bf16, the configuration's own precision, well under it."""
    import jax.numpy as jnp

    low = _compare(pair, operand_dtype=jnp.float8_e4m3fn)
    assert not low["ok"] and low["logits_err"] > 2 * low["logits_tol"], low
    own = _compare(pair, operand_dtype=jnp.bfloat16)
    assert own["ok"] and own["logits_err"] < 0.5 * own["logits_tol"], own


def test_reference_signature_is_the_harnesses_and_it_imports_no_operator(
        pair):
    cfg, sizes, params, x, y, _, _ = pair
    loss, logits = cfg.reference(params, x, y, sizes)
    assert logits.shape == x.shape + (sizes["vocab_size"],)
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError, match="without="):
        cfg.reference(params, x, y, sizes, without=("rope",))
    # the file imports nothing of the program (build() is handed it), and
    # the reference names none of its operators
    with open(cfg.__file__) as f:
        text = f.read()
    assert "import mxnet_tpu" not in text and "from mxnet_tpu" not in text
    assert "mx." not in inspect.getsource(cfg.reference)


@pytest.mark.parametrize("term", [
    "beta_double",       # beta = 2 x sigmoid: linear_allow_neg_eigval
    "decay",             # alpha_t: the gate on the state
    "delta_term",        # - beta (S k) k^T: the rule's correction
    "q_l2norm",          # q / |q|
    "k_l2norm",          # k / |k|
    "key_scale",         # d_k^-1/2 on the queries
    "convolution",       # the three short convolutions
    "output_gate",       # silu(g_proj x) on the normed heads
    "head_norm",         # the norm over each head's values
    "qk_norm",           # the full layer's norms on queries and keys
    "post_norm",         # the norm AFTER each sub-block
])
def test_a_missing_term_fails_the_configurations_tolerance(pair, term):
    assert term in pair[0].TERMS
    out = _compare(pair, without=(term,))
    assert not out["ok"], (term, out)


def test_every_term_has_its_case():
    want = set(manifest.load_module("configs", "olmo_hybrid_7b_tp2").TERMS)
    marks = test_a_missing_term_fails_the_configurations_tolerance.pytestmark
    assert set(marks[0].args[1]) == want


# -- operation counts against hand counts, at the published widths ------------
def test_one_layer_of_each_kind_by_hand():
    c = manifest.resolve(CELL)
    cfg, s = c.config_module, c.sizes
    # a token through a delta-rule layer's held projections: q and k
    # 3840 x 1440 each, v and the gate 3840 x 2880 each, the two gates'
    # rows 3840 x 15 each, and 2880 x 3840 back
    assert cfg.linear_proj_macs(s, 1) == 2 * 5_529_600 + 2 * 11_059_200 \
        + 2 * 57_600 + 11_059_200 == 44_352_000
    # the rule, a chunk of 64 tokens of one head: K_beta K^T, Q K^T and
    # T (K_beta e^g) 64 x 64 x 96 each, T V_beta and the masked scores
    # times V' 64 x 64 x 192 each, and W S, Q S, K^T V' 64 x 96 x 192 each
    a_chunk = 3 * 393_216 + 2 * 786_432 + 3 * 1_179_648
    assert a_chunk == 6_291_456
    assert cfg.delta_rule_macs(s, 8192) == 128 * 15 * a_chunk \
        == 8192 * 1_474_560
    # the full layer: four projections of 3840 x 1920, the causal core
    # 15 heads x (128 + 128) x 8192 keys at half the square
    assert cfg.full_proj_macs(s, 1) == 29_491_200
    assert cfg.full_core_macs(s, 8192) == 8192 * 15 * 256 * 4096 \
        == 8192 * 15_728_640
    # the feed-forward's three products of 3840 x 11008, the head 3840 x 12544
    assert cfg.ffn_macs(s, 1) == 126_812_160
    per_token = 3 * (44_352_000 + 1_474_560 + 126_812_160) \
        + (29_491_200 + 15_728_640 + 126_812_160) + 48_168_960
    assert per_token == 738_117_120
    assert cfg.forward_macs(s, 8192) == 8192 * per_token
    assert cfg.ops_per_sample(s, c.mix) == opcount.train_ops(8192 * per_token)
    # the issue's figure: 36 TFLOP a trained sequence
    assert round(cfg.ops_per_sample(s, c.mix) / 1e12, 2) == 36.28


def test_the_cut_has_766241946_parameters_without_allocating_them():
    sizes = manifest.resolve(CELL).sizes
    published = {k: sizes["published_heads"]
                 for k in ("num_attention_heads", "num_key_value_heads",
                           "linear_num_key_heads", "linear_num_value_heads")}
    net = mx.gluon.model_zoo.olmo_hybrid.olmo_hybrid(
        {**sizes, **published},
        held_heads=range(sizes["num_attention_heads"]))
    params = net.collect_params()
    assert all(p._data is None for p in params.values())   # never initialised

    def count(prefix):
        return sum(int(np.prod(p.shape)) for n, p in params.items()
                   if n.startswith(prefix))

    # a delta-rule mixer: the projections' 44,352,000, three convolutions
    # of 4 taps over 1,440 + 1,440 + 2,880 channels, A_log and dt_bias a
    # head, one norm scale of 192
    assert count("model.layers.0.mixer.") == 44_352_000 + 23_040 + 30 + 192 \
        == 44_375_262
    assert count("model.layers.0.mlp.") == 126_812_160
    for i in (0, 1, 2):                     # with the two norms after
        assert count(f"model.layers.{i}.") == 171_195_102
    # the full layer: four projections and QK-norm's two scales of 1,920
    assert count("model.layers.3.mixer.") == 29_491_200 + 3_840
    assert count("model.layers.3.") == 156_314_880
    assert count("model.embed_tokens.") == count("lm_head.") == 48_168_960
    assert count("model.norm.") == 3_840
    assert count("") == 766_241_946
    # every vector the issue lists, and no bias
    assert params["model.layers.0.mixer.a_proj.weight"].shape == (15, 3840)
    assert params["model.layers.0.mixer.o_proj.weight"].shape == (3840, 2880)
    assert params["model.layers.3.mixer.q_norm.gamma"].shape == (1920,)
    assert not [n for n in params if n.endswith("bias")
                and not n.endswith("dt_bias")]


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
    assert sizes["reduced"] == [
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "linear_num_key_heads", "linear_num_value_heads", "vocab_size"]
    assert sizes["published_heads"] == row["config"]["num_attention_heads"] \
        == 2 * sizes["num_attention_heads"]
    assert sizes["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert sizes["layer_types"][:4] == ["linear_attention"] * 3 \
        + ["full_attention"]
    assert sizes["head_dim"] * sizes["published_heads"] \
        == sizes["hidden_size"]
    assert "two chips" in sizes["deployment"].lower()
    assert sizes["check"]["why"] and sizes["loss_fall_why"]
    assert {"norm_placement", "qk_norm", "rope_theta", "output_gate",
            "conv_activation", "conv_bias", "qk_l2norm", "gates",
            "A_log_dt_bias", "chunk_size", "init", "optimizer_params"} \
        <= set(sizes["assumed"])
    mix = manifest.resolve(CELL).mix
    assert (mix["seq_len"], mix["batch_per_chip"],
            mix["batch_candidates"]) == (8192, 1, [1])
    assert mix["sizing"]["step_gib"] and mix["env"] == {
        "MXNET_SPMD_MESH": "off"}


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
NEW_READERS = ("kernel.delta_mixer_share", "kernel.delta_rule_share",
               "delta_rule_roofline", "kernel.full_core_share",
               "kernel.dense_ffn_share")


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_is_in_place_for_its_entry(name):
    """The readers are here, their ``per_layer`` entries are not: PR 38's
    check refused five entries in front of PR 35's eight, and
    ``test_host_view.py`` refuses any after them, so a ``benchmark`` issue
    appends the five (``PERF.md`` section 7 has them) with that pin relaxed."""
    assert callable(manifest.load_module("layer_metrics", name).read)


def test_rehearsal_of_a_traced_run_goes_through_the_new_driver(capsys):
    """A fraction of a sequence is run as one sequence, the comparison is
    ``train_fixed_shape``'s own, and the CPU has no device plane: this
    cell's readers, all of the device trace, leave the line alone."""
    assert manifest.resolve(CELL).mix["kind"] == "train_fixed_shape_seq"
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 77),
                   "--seconds", "1", "--trace", "1", "--rehearse"],
                  t_start=time.perf_counter())
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    line = json.loads(out.out.strip().splitlines()[-1][len("REHEARSAL "):])
    assert line["correct"] is True, line["checks"]
    check = line["checks"]["reference_check"]
    assert set(check) == {"ok", "logits_err", "logits_tol", "loss",
                          "reference_loss", "loss_err", "loss_tol"}
    got = line["metrics"]
    assert got["step.dispatches_per_step"]["value"] == 1.0
    assert not set(NEW_READERS) & set(got)
    assert "steps of 1 in" in out.err and "memory by device" in out.err


def test_the_trace_readers_on_a_view_by_hand(monkeypatch):
    """Every new device-trace reader on a hand-made ``scope_view``: one step
    of 1 s (the rows below are its percents), rows named as the program's
    scopes name them."""
    lm, seq = "OlmoHybridForCausalLM", "HybridSequential"
    layer = [lm, "OlmoHybridModel", seq, "OlmoHybridDecoderLayer"]
    delta, full = layer + ["GatedDeltaNet"], layer + ["OlmoHybridAttention"]
    rows = [
        (delta + ["Dense"], 9e-3), (delta + ["CausalConv1d"], 2e-3),
        (delta + ["DeltaRule"], 12e-3), (delta, 3e-3),
        (full + ["Dense"], 4e-3), (full + ["RMSNorm"], 1e-3), (full, 2.5e-3),
        (layer + ["OlmoHybridMLP", "Dense"], 50e-3),
        (layer + ["OlmoHybridMLP"], 2e-3),
        (layer + ["RMSNorm"], 1e-3),
        ([lm, "Dense"], 5e-3),
        (["SoftmaxCrossEntropyLoss"], 0.5e-3),
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

    assert read("kernel.delta_mixer_share") == pytest.approx(26.0)
    assert read("kernel.delta_rule_share") == pytest.approx(12.0)
    # the block ITSELF: neither its projections nor QK-norm
    assert read("kernel.full_core_share") == pytest.approx(2.5)
    assert read("kernel.dense_ffn_share") == pytest.approx(52.0)
    # three layers' chunk products at 8,192 tokens, three passes
    rule_ops = 6 * 3 * 8192 * 1_474_560
    assert read("delta_rule_roofline") == pytest.approx(
        100 * rule_ops / 197e12 / 0.12)
    for name in NEW_READERS:
        assert 0 <= read(name) <= 100, name
    # the roofline counts from the obs it is given, and a driver that
    # states no sizes gives it nothing to read
    bare = {k: v for k, v in obs.items() if k != "sizes"}
    assert manifest.load_module("layer_metrics",
                                "delta_rule_roofline").read(bare) is None
    # a program without the scopes (the parent), or a run without a device
    # trace: nothing to read, nothing raised
    monkeypatch.setattr(scope_view, "traced", lambda obs: None)
    for name in NEW_READERS:
        assert read(name) is None, name
