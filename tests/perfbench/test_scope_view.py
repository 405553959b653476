"""perfbench/scope_view.py on a small recorded trace and a small HLO text:
every expected value is worked by hand from the intervals in
data/scoped_step.xplane.txt and the op_names in data/scoped_step.hlo.txt.
CPU only.

Chip 0 (ns; the step program runs [0,1100], a small program [1110,1120]):

    fusion.1   0-200     forward   Toy/BERTSelfAttention.attn/Dense.qkv
    fusion.2   200-260   forward   Toy/BERTMaskedLMHead.head/LayerNorm.ln
    fusion.3   260-300   rest      Toy/BERTSelfAttention.attn/Dropout.dropout
    fusion.4   300-700   backward  .../Dense.qkv   (holds an update too)
    while.1    700-800   update    optimizer_update   (self 40)
    fusion.5   720-780   update    optimizer_update/while/body
    fusion.6   800-850   rest      loss_scale
    copy.9     850-900   rest      no op_name: unscoped
    fusion.7   900-960   backward  .../LayerNorm.ln   (holds a forward too)
    fusion.8   960-1000  backward  SoftmaxCrossEntropyLoss
    fusion.9   1000-1100 forward   Toy/BERTSelfAttention.attn  (softmax)
    convert.1  1110-1120 rest      a program op_names does not know

Chip 1 (the step program runs [0,500]): fusion.1 0-100, fusion.4 100-500.
"""
import json
import os
import time

import pytest

import mxnet_tpu as mx
from perfbench import manifest, run, scope_view
from perfbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NS = 1e-9
BUSY = 805.0                  # mean of chip 0's 1110 and chip 1's 500
NEW_READERS = ["kernel.backward_over_forward", "kernel.update_share",
               "kernel.norm_share", "kernel.attention_core_share",
               "kernel.vocab_head_share", "kernel.unscoped_share"]


def _names():
    with open(os.path.join(DATA, "scoped_step.hlo.txt")) as f:
        return {n.module: n for n in mx.program_store.parse_op_names(f.read())}


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    """The text-form trace as the driver would have left it:
    ``<root>/.perfbench_out/<cell>/trace/plugins/profile/<time>/*.xplane.pb``.
    Returns ``(root, path)``."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "scoped_step.xplane.txt")) as f:
        serialized = ProfileData.text_proto_to_serialized_xspace(f.read())
    root = tmp_path_factory.mktemp("checkout")
    directory = root / ".perfbench_out" / "toy_cell" / "trace" / "plugins" \
        / "profile" / "2026_09_28"
    directory.mkdir(parents=True)
    path = directory / "host.xplane.pb"
    path.write_bytes(serialized)
    return root, path


@pytest.fixture(scope="module")
def view(trace_file):
    events, _start = tr.load_xplane(str(trace_file[1]))
    return scope_view.view(events, _names(), mx.cached_step.STEP_SCOPES,
                           mx.program_store.pass_of, steps=1)


def test_window_busy_and_time_by_pass(view):
    assert view["window_s"] == pytest.approx(1120 * NS)
    assert view["chips"] == 2 and view["steps"] == 1
    assert view["busy_s"] == pytest.approx(BUSY * NS)
    # chip 0: forward 200+60+100, backward 400+60+40, update 40+60,
    # rest 40+50+50+10; chip 1: forward 100, backward 400
    assert view["pass_s"] == pytest.approx(
        {"forward": 230 * NS, "backward": 450 * NS, "update": 50 * NS,
         "rest": 75 * NS})
    assert sum(view["pass_s"].values()) == pytest.approx(view["busy_s"])


def test_busy_and_window_are_the_benchmarks_own(view, trace_file):
    """``traced`` tells a stale file by its window, and the passes must add
    up to the busy time ``trace_reduce`` reads from the same events."""
    events, _start = tr.load_xplane(str(trace_file[1]))
    reduced = tr.reduce(events, steps=1)
    assert view["window_s"] == reduced["window_s"]
    assert view["busy_s"] == pytest.approx(reduced["busy_s"])


def test_rows_by_pass_class_path_and_step_scope(view):
    rows = {(r["pass"], tuple(r["classes"]), r["step_scope"]): r["s"]
            for r in view["rows"]}
    dense = ("Toy", "BERTSelfAttention", "Dense")
    assert rows["forward", dense, None] == pytest.approx(150 * NS)
    assert rows["backward", dense, None] == pytest.approx(400 * NS)
    assert rows["rest", ("Toy", "BERTSelfAttention", "Dropout"), None] \
        == pytest.approx(20 * NS)
    assert rows["forward", ("Toy", "BERTSelfAttention"), None] \
        == pytest.approx(50 * NS)
    assert rows["update", (), "optimizer_update"] == pytest.approx(50 * NS)
    assert rows["rest", (), "loss_scale"] == pytest.approx(25 * NS)
    assert rows["rest", (), None] == pytest.approx(30 * NS)     # unscoped
    assert sum(rows.values()) == pytest.approx(BUSY * NS)
    assert view["step_scope_s"] == pytest.approx(
        {"optimizer_update": 50 * NS, "loss_scale": 25 * NS})


def test_instances_keep_the_registered_key(view):
    path, total, forward, backward = view["instances"][0]
    assert path == "Toy/BERTSelfAttention.attn/Dense.qkv"
    assert (total, forward, backward) == pytest.approx(
        (550 * NS, 150 * NS, 400 * NS))
    assert "Toy/BERTMaskedLMHead.head/LayerNorm.ln" in [
        i[0] for i in view["instances"]]


def test_unscoped_and_unknown_programs(view):
    # chip 0's copy.9 (no op_name) and the small program's convert.1
    assert view["unscoped_s"] == pytest.approx(30 * NS)
    assert view["unknown_program_s"] == pytest.approx(5 * NS)
    assert view["unscoped_ops"] == [
        ["jit_mx_train_step__Toy: copy.9 [copy]", pytest.approx(25 * NS)],
        ["jit_convert_element_type: convert.1 [convert]",
         pytest.approx(5 * NS)]]


def test_time_in_fusions_that_span_passes(view):
    # fusion.4 holds a backward product and an update (400 on both chips);
    # fusion.7 a forward and a backward multiply (60 on chip 0)
    assert view["cross_pass_s"] == pytest.approx(
        {"backward+update": 400 * NS, "backward+forward": 30 * NS})


def test_programs_by_module_name(view):
    assert view["modules"] == [
        ["jit_mx_train_step__Toy", 1.0, pytest.approx(800 * NS)],
        ["jit_convert_element_type", 0.5, pytest.approx(5 * NS)]]


def test_a_program_nobody_knows_is_all_unscoped(trace_file):
    events, _start = tr.load_xplane(str(trace_file[1]))
    v = scope_view.view(events, {}, mx.cached_step.STEP_SCOPES,
                        mx.program_store.pass_of)
    assert v["unscoped_s"] == pytest.approx(BUSY * NS)
    assert v["unknown_program_s"] == pytest.approx(BUSY * NS)
    assert v["pass_s"]["rest"] == pytest.approx(BUSY * NS)
    host_only = [tr.Event("/host:CPU", "python", "dot_general.1", 0.0, 5.0)]
    assert scope_view.view(host_only, {}, (), mx.program_store.pass_of) \
        is None


@pytest.mark.parametrize("op_name, blocks, step_scope", [
    ("jit(mx_train_step__MLM)/transpose(jvp(jit(fwd_fn)))/MLM/"
     "BERTModel.encoder/HybridSequential.layers/BERTEncoderLayer.3/"
     "BERTSelfAttention.attn/Dense.qkv/dot_general",
     ("MLM", "BERTModel.encoder", "HybridSequential.layers",
      "BERTEncoderLayer.3", "BERTSelfAttention.attn", "Dense.qkv"), None),
    # a block that is not hybridized sits inside jax's wrapper
    ("jit(mx_train_step__Net)/jvp(Net)/Dense.0/Activation.act/jit(relu)/max",
     ("Net", "Dense.0", "Activation.act"), None),
    ("jit(s)/Net/Dense.a/transpose(jvp())/mul", ("Net", "Dense.a"), None),
    ("jit(mx_train_step__Net)/optimizer_update/while/body/mul", (),
     "optimizer_update"),
    ("jit(s)/sentinel_digest/cond/branch_1_fun/reduce_sum", (),
     "sentinel_digest"),
    ("jit(s)/jvp(Net)/add;jit(s)/optimizer_update/mul", ("Net",), None),
    # a nested jit is itself the primitive of its call instruction
    ("jit(s)/jvp(L2Loss)/jit(take_along_axis)", ("L2Loss",), None),
    ("jit(s)/jvp()/jit(_threefry_split)/next_key/while/body/add", (), None),
    ("reduce_sum", (), None),
    ("", (), None),
    (None, (), None),
])
def test_scopes_of(op_name, blocks, step_scope):
    assert scope_view.scopes_of(op_name, mx.cached_step.STEP_SCOPES) \
        == (blocks, step_scope)
    assert [scope_view.class_of(b) for b in blocks] \
        == [b.split(".")[0] for b in blocks]


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
@pytest.fixture
def traced_process(trace_file, monkeypatch):
    """A process whose driver has just left the toy trace and whose
    program store knows the toy program."""
    root, _path = trace_file
    monkeypatch.setattr(scope_view, "ROOT", str(root))
    monkeypatch.setattr(scope_view, "_CACHE", {})
    monkeypatch.setattr(mx.program_store, "op_names",
                        lambda name=None: _names())
    return {"trace": {"window_s": 1120 * NS, "steps": 1}}


@pytest.mark.parametrize("metric, want", [
    ("kernel.backward_over_forward", 450 / 230),
    ("kernel.update_share", 100 * 50 / BUSY),
    # fusion.2 and fusion.7 (60 each, chip 0): innermost LayerNorm
    ("kernel.norm_share", 100 * 60 / BUSY),
    # fusion.9 (100) and the dropout under the attention block (40), not
    # its Dense child
    ("kernel.attention_core_share", 100 * 70 / BUSY),
    # under the head (60 + 60) or the loss block (40)
    ("kernel.vocab_head_share", 100 * 80 / BUSY),
    ("kernel.unscoped_share", 100 * 30 / BUSY),
])
def test_readers_on_the_toy_trace(traced_process, metric, want):
    read = manifest.load_module("layer_metrics", metric).read
    assert read(traced_process) == pytest.approx(want)


@pytest.mark.parametrize("norm", sorted(
    n for n in dir(mx.gluon.nn)
    if "Norm" in n and isinstance(getattr(mx.gluon.nn, n), type)))
def test_every_norm_class_counts_where_it_is_innermost(monkeypatch, norm):
    """A decoder's norms are ``RMSNorm`` blocks: each normalisation class of
    ``gluon.nn`` (the scope is the class's own name) counts as the innermost
    block of a row, and none counts where a block of its own stands below
    it."""
    layer = ["NemotronHForCausalLM", "NemotronHModel", "NemotronHBlock"]
    rows = [(layer + [norm], 3e-3), (layer + ["NemotronHAttention"], 5e-3),
            (layer + [norm, "Dense"], 1e-3), (layer + ["Dense"], 1e-3)]
    view = {"steps": 1, "busy_s": 0.1,
            "rows": [{"pass": "forward", "classes": c, "step_scope": None,
                      "s": s} for c, s in rows]}
    monkeypatch.setattr(scope_view, "traced", lambda obs: view)
    read = manifest.load_module("layer_metrics", "kernel.norm_share").read
    assert read({"trace": {"steps": 1}}) == pytest.approx(3.0)


def test_the_view_is_cached_written_and_rendered(traced_process, trace_file):
    v = scope_view.traced(traced_process)
    assert scope_view.traced(traced_process) is v       # one reduction
    assert set(v["reader_s"]) == {"load_trace", "op_names", "view"}
    assert v["trace_file"].startswith(".perfbench_out/toy_cell/trace/")
    written = trace_file[0] / ".perfbench_out" / "toy_cell" \
        / "scope_view.json"
    with open(written) as f:
        assert json.load(f)["busy_s"] == pytest.approx(BUSY * NS)
    text = scope_view.render(v)
    for title in ("Device time by pass", "By innermost block class",
                  "The costliest block instances", "By step scope",
                  "Unscoped operations", "Fusions that span passes",
                  "Programs", "Seconds the readers took"):
        assert title in text
    # ms a step by pass, then % of busy: (60 + 60) / 2 of 805 ns
    assert "| LayerNorm | 3e-05 | 3e-05 | 0 | 0 | 7.453 |" in text
    assert "| jit_convert_element_type | 0.5 | 5e-06 |" in text


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_stale_trace_reads_as_nothing(traced_process, metric):
    """Another run's file (its window is not the one the driver reduced)
    is never read."""
    traced_process["trace"]["window_s"] = 1121 * NS
    read = manifest.load_module("layer_metrics", metric).read
    assert read(traced_process) is None


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_rehearsal_without_a_device_plane_reads_as_nothing(metric,
                                                             monkeypatch):
    monkeypatch.setattr(scope_view, "_CACHE", {})
    read = manifest.load_module("layer_metrics", metric).read
    assert read({"trace": None}) is None


def test_a_program_without_op_names_reads_as_nothing(traced_process,
                                                     monkeypatch):
    """The parent of the PR that added ``program_store.op_names``: the
    benchmark's new files are laid over it, and must not raise."""
    monkeypatch.delattr(mx.program_store, "op_names")
    for metric in NEW_READERS:
        read = manifest.load_module("layer_metrics", metric).read
        assert read(traced_process) is None


def test_no_trace_on_disk_reads_as_nothing(traced_process, tmp_path,
                                           monkeypatch):
    monkeypatch.setattr(scope_view, "ROOT", str(tmp_path))
    assert scope_view.traced(traced_process) is None


def test_the_new_entries_name_their_cells():
    per_layer = {m["name"]: m for m in manifest.load_benchmark()["per_layer"]}
    bert = ["bert_base_train_s512", "bert_base_train_s128"]
    for name in NEW_READERS:
        entry = per_layer[name]
        assert (entry["layer"], entry["source"], entry["moves"]) \
            == ("kernels", "device_trace", "mfu")
        assert entry.get("workloads") == (
            bert if name in ("kernel.attention_core_share",
                             "kernel.vocab_head_share") else None)
    in_resnet = {m["name"] for m in manifest.resolve("resnet50_train").per_layer}
    assert len(in_resnet & set(NEW_READERS)) == 4
    in_bert = {m["name"]
               for m in manifest.resolve("bert_base_train_s128").per_layer}
    assert set(NEW_READERS) <= in_bert


def test_a_rehearsed_step_leaves_only_known_operations_unscoped(capsys):
    """Counts, not times: of everything jax traced into the one-layer
    BERT's step program, only what the user's loss function does outside
    any block (``.mean()``) and the key split a compiled block makes
    before its forward sit under no block and no step scope, both directly
    under the differentiated call (``jvp()``).  Anything else here is a
    scope someone forgot."""
    rc = run.main(["--workload", "bert_base_train_s128", "--seed", "5",
                   "--seconds", "0.2", "--rehearse"],
                  t_start=time.perf_counter())
    assert rc == 0, capsys.readouterr().err[-3000:]
    names = mx.program_store.op_names("train_step")["jit_mx_train_step__MLM"]
    traced = [op for op in names.op_name.values()
              if op.startswith("jit(mx_train_step__MLM)/")]
    scoped = [op for op in traced if scope_view.scopes_of(
        op, mx.cached_step.STEP_SCOPES) != ((), None)]
    unscoped = set(traced) - set(scoped)
    assert len(scoped) > 20 * len(unscoped)
    # ... and the call of the compiled block's own program
    assert all("/jvp()/" in op or "/transpose(jvp())/" in op
               or op.endswith("jvp(jit(fwd_fn))")
               for op in unscoped), sorted(unscoped)
    for cls in ("BERTSelfAttention.attn", "BERTMaskedLMHead.head",
                "SoftmaxCrossEntropyLoss", "LayerNorm.ln1", "Dropout"):
        assert any(cls in op for op in scoped), cls
