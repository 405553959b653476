"""perfbench/host_view.py on a small recorded trace and a hand-written list of
the program's spans: every expected value is worked by hand from the
intervals in data/host_gaps.xplane.txt and data/host_gaps.spans.json (times
in ns after the trace's start).  CPU only.

Chip 0, the more idle one (the window is [90,1900]: chip 1 runs [90,1900]):

    convert      100-110    a small program
    step run 1   195-600    operations 200-400 and 450-600 (as on the chip,
                            a run's event begins before its first operation)
    convert      700-710
    step run 2   900-1300
    convert      1400-1410
    step run 3   1500-1800

The host (``launch`` spans end at L = 160, 690, 1440):

    step 1  prep 40-80     operands 80-120     launch 120-160    writeback 160-175
    step 2  prep 620-650   operands 650-670    launch 670-690    writeback 690-700
    step 3  prep 1310-1350 operands 1350-1420  launch 1420-1440  writeback 1440-1450
    gate (a drain between steps) 1810-1830;  step 4 (timed window) 2400-2530

The gaps of chip 0, 730 ns in all:

    90-100     ends in a small program: the host's, under operands    10
    110-200    run 1, L = 160: operands 10, launch 40 | launched      40
    400-450    inside run 1, L = 160 < 400: launched                  50
    600-700    a small program: outside 20, prep 30, operands 20,
               launch 20, writeback 10
    710-900    run 2, L = 690 < 710: launched                        190
    1300-1400  a small program: outside 10, prep 40, operands 50
    1410-1500  run 3, L = 1440: operands 10, launch 20 | launched     60
    1800-1900  no run ends it: gate 20, outside 80

The first two begin before run 1 does: the refill, 100 ns with 40
launched; of the other 630 ns, 300 are launched.
"""
import copy
import json
import os
import time

import pytest

import mxnet_tpu as mx
from perfbench import host_view, manifest, run, scope_view
from perfbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NS = 1e-9
START = 1_790_000_000_000_000_000
WANT = {"launched": 340, "prep": 70, "operands": 100, "launch": 80,
        "writeback": 10, "gate": 20, "outside_step": 110}
ROWS = {      # name: unit, better, source, layer, moves (ISSUE 35's table)
    "step.prep_ms_per_step":
        ("ms", "lower", "program_span", "step_scheduler", "samples_per_s"),
    "step.operands_ms_per_step":
        ("ms", "lower", "program_span", "step_scheduler", "samples_per_s"),
    "step.launch_ms_per_step":
        ("ms", "lower", "program_span", "step_scheduler", "samples_per_s"),
    "step.writeback_ms_per_step":
        ("ms", "lower", "program_span", "step_scheduler", "samples_per_s"),
    "input.transfer_ms_per_step":
        ("ms", "lower", "program_span", "input", "samples_per_s"),
    "input.slot_wait_share":
        ("%", "higher", "program_span", "input", "samples_per_s"),
    "programs.trace_s":
        ("s", "lower", "program_counter", "program_store", "setup_s"),
    "device.idle_launched_share":
        ("%", "higher", "device_trace", "device", "samples_per_s"),
}


def _spans(keep=lambda s: True):
    """The fixture's spans as ``telemetry.spans()`` returns them."""
    with open(os.path.join(DATA, "host_gaps.spans.json")) as f:
        records = json.load(f)
    out = []
    for seq, s in enumerate(records, 1):
        t0, t1 = s.pop("at")
        out.append(dict(s, t0_ns=START + t0, t1_ns=START + t1,
                        t0_us=(START + t0) // 1000, dur_us=1, seq=seq))
    return [s for s in out if keep(s)]


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    """The text-form trace where the driver would have left it."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "host_gaps.xplane.txt")) as f:
        serialized = ProfileData.text_proto_to_serialized_xspace(f.read())
    root = tmp_path_factory.mktemp("checkout")
    directory = root / ".perfbench_out" / "toy_cell" / "trace" / "plugins" \
        / "profile" / "2026_10_04"
    directory.mkdir(parents=True)
    path = directory / "host.xplane.pb"
    path.write_bytes(serialized)
    return root, path


@pytest.fixture(scope="module")
def events(trace_file):
    loaded, start = tr.load_xplane(str(trace_file[1]))
    assert start == START
    return loaded


@pytest.fixture(scope="module")
def view(events):
    return host_view.view(events, START, _spans(), steps=3)


# ---------------------------------------------------------------------------
# the classes of idle time
# ---------------------------------------------------------------------------
def test_the_worst_chip_and_its_idle_time(view, events):
    assert view["worst_plane"] == "/device:TPU:0" and view["chips"] == 2
    assert view["window_s"] == pytest.approx(1810 * NS)
    assert view["idle_s"] == pytest.approx(730 * NS)
    assert view["gap_count"] == 8
    # what trace_reduce calls device.idle_share, of the same chip
    reduced = tr.reduce(events, steps=3)
    assert view["idle_share"] == pytest.approx(
        max(d["idle_share"] for d in reduced["devices"]))


def test_the_classes_sum_to_the_idle_time_to_the_nanosecond(view):
    got = {c: round(s / NS, 6) for c, s in view["idle_class_s"].items()}
    assert got == WANT
    assert sum(got.values()) == 730


def test_the_refill_is_left_out_of_the_launched_share(view, events):
    """The trace starts with nothing in flight: what the chip idles before
    the first step program runs says where the trace starts."""
    assert view["refill_s"] == pytest.approx(100 * NS)
    assert view["launched_share"] == pytest.approx(100 * 300 / 630)
    assert [round(g["start_s"] / NS) for g in view["gaps"] if g["refill"]] \
        == [110, 90]
    # a trace whose only idle time is the refill says nothing of the loop
    first = [e for e in events
             if e.plane.endswith("TPU:0") and e.start_ns < 450]
    alone = host_view.view(first, START, _spans(), steps=1)
    assert alone["refill_s"] == alone["idle_s"] == pytest.approx(90 * NS)
    assert alone["launched_share"] is None


def _gap(view, start):
    return next(g for g in view["gaps"]
                if g["start_s"] == pytest.approx(start * NS, abs=1e-12))


@pytest.mark.parametrize("start, ended_by, classes", [
    # the launch had ended before the gap began
    (710, "jit_mx_train_step__Toy", {"launched": 190}),
    # ... or the chip stopped inside the running program
    (400, "jit_mx_train_step__Toy", {"launched": 50}),
    # the launch ends inside the gap: split at L
    (110, "jit_mx_train_step__Toy",
     {"operands": 10, "launch": 40, "launched": 40}),
    (1410, "jit_mx_train_step__Toy",
     {"operands": 10, "launch": 20, "launched": 60}),
    # a small program ends it: the host's whole
    (600, "jit_convert_element_type",
     {"outside_step": 20, "prep": 30, "operands": 20, "launch": 20,
      "writeback": 10}),
    (1300, "jit_convert_element_type",
     {"outside_step": 10, "prep": 40, "operands": 50}),
    (90, "jit_convert_element_type", {"operands": 10}),
    # under no phase (and ended by no run): the user's loop
    (1800, None, {"gate": 20, "outside_step": 80}),
])
def test_a_gap_by_class(view, start, ended_by, classes):
    gap = _gap(view, start)
    assert gap["ended_by"] == ended_by
    assert {c: round(s / NS, 6) for c, s in gap["class_s"].items()} == classes


def test_runs_and_launches_match_one_to_one(view):
    assert view["matched"] == "one_to_one"
    # the warm-up's launch (before the trace) and the timed window's (after
    # its last device operation) are not the traced window's
    assert view["runs"] == {"step": 3, "grad": 0, "update": 0}
    assert view["launches"] == view["runs"]


def test_phases_by_traced_step_transfers_and_builds(view):
    assert [s["step"] for s in view["steps_traced"]] == [1, 2, 3]
    first = view["steps_traced"][0]
    assert {k: round(v * 1e6, 6) for k, v in first.items() if k != "step"} \
        == {"host_ms": 135, "prep": 40, "operands": 40, "launch": 40,
            "writeback": 15}
    assert view["phase_ms_per_step"]["launch"] * 1e6 \
        == pytest.approx((40 + 20 + 20) / 3)
    assert view["phase_ms_per_step"]["host_ms"] * 1e6 \
        == pytest.approx((135 + 80 + 140) / 3)
    # the prefetcher's thread inside the traced window
    assert view["transfers"]["count"] == 2
    assert view["transfers"]["bytes"] == 3000
    assert view["transfers"]["seconds"] == pytest.approx((120 + 175) * NS)
    assert view["transfers"]["slot_wait_s"] == pytest.approx(
        (280 + 410) * NS)
    under = _gap(view, 710)["transfers"]
    assert [(round(t["start_s"] / NS), round(t["end_s"] / NS), t["bytes"])
            for t in under] == [(705, 880, 2000)]
    assert [t["bytes"] for t in _gap(view, 400)["transfers"]] == [1000]
    build, = view["builds"]
    assert build == {"module": "jit_mx_train_step__Toy",
                     "namespace": "train_step", "in_step": True,
                     "build_s": pytest.approx(2000 * NS),
                     "trace_s": pytest.approx(990 * NS),
                     "compile_s": pytest.approx(990 * NS),
                     "cache": "hit", "retrieval_s": 0.5}
    assert view["builds_by_namespace"]["train_step"]["hit"] == 1


# ---------------------------------------------------------------------------
# what gives None
# ---------------------------------------------------------------------------
def test_a_ring_that_lost_the_first_traced_step_reads_as_nothing(events):
    """Nothing older than the trace's start is left: the first traced step
    may be among what the ring dropped."""
    newer = _spans(lambda s: s["t0_ns"] >= START)
    assert host_view.view(events, START, newer, steps=3) is None


def test_spans_on_no_clock_read_as_nothing(events):
    """The parent of ISSUE 35's PR: its records have ``t0_us`` on another
    clock and no ``t0_ns``."""
    old = [{k: v for k, v in s.items() if k not in ("t0_ns", "t1_ns")}
           for s in _spans()]
    assert host_view.view(events, START, old, steps=3) is None
    assert host_view.view(events, START, [], steps=3) is None


def test_a_lost_device_event_matches_the_newest_launches(events):
    """One launch more than runs (the profiler lost a device event): the
    newest runs take the newest launches, and the view says so."""
    spans = _spans()
    extra = dict(next(s for s in spans if s["id"] == "s1l"), id="s0l",
                 parent=None, t0_ns=START + 10, t1_ns=START + 20)
    v = host_view.view(events, START, spans + [extra], steps=3)
    assert v["matched"] == "tail"
    assert v["launches"]["step"] == 4 and v["runs"]["step"] == 3
    assert {c: round(s / NS, 6) for c, s in v["idle_class_s"].items()} \
        == WANT


def _launch_after_its_run(spans):
    """Run 3 starts at 1500: its launch now starts 100 ns after it."""
    launch = next(s for s in spans if s["id"] == "s3l")
    launch.update(t0_ns=START + 1600, t1_ns=START + 1620)
    return spans


@pytest.mark.parametrize("what", ["more_runs_than_launches",
                                  "a_run_before_its_launch"])
def test_runs_that_cannot_be_matched_read_as_nothing(events, what,
                                                     monkeypatch):
    if what == "more_runs_than_launches":
        spans = _spans(lambda s: s["id"] != "s2l")
    else:
        # by more than the two clocks' error, at this fixture's scale
        monkeypatch.setattr(host_view, "CLOCK_SLACK_NS", 50)
        spans = _launch_after_its_run(_spans())
    assert host_view.view(events, START, spans, steps=3) is None


def test_a_run_inside_the_clocks_slack_is_its_launchs(events, monkeypatch):
    """The device trace lies on ``time.time_ns`` to about a millisecond: a
    run that begins less than ``CLOCK_SLACK_NS`` before its launch span is
    still that launch's, and the gap it ends is the host's whole."""
    monkeypatch.setattr(host_view, "CLOCK_SLACK_NS", 200)
    v = host_view.view(events, START, _launch_after_its_run(_spans()),
                       steps=3)
    assert v["matched"] == "one_to_one"
    classes = {c: round(s / NS, 6)
               for c, s in _gap(v, 1410)["class_s"].items()}
    assert classes == {"operands": 10, "writeback": 10, "outside_step": 70}
    # and at the chip's slack, the fixture's nanoseconds are well inside it
    monkeypatch.undo()
    assert host_view.CLOCK_SLACK_NS == 2_000_000
    assert host_view.view(events, START, _launch_after_its_run(_spans()),
                          steps=3)["matched"] == "one_to_one"


@pytest.fixture
def traced_process(trace_file, monkeypatch):
    """A process whose driver has just left the toy trace and whose rings
    hold the toy spans."""
    root, _path = trace_file
    monkeypatch.setattr(scope_view, "ROOT", str(root))
    monkeypatch.setattr(host_view, "_CACHE", {})
    monkeypatch.setattr(
        mx.telemetry, "spans",
        lambda cat=None, **kw: _spans(lambda s: cat in (None, s["cat"])))
    return {"trace": {"window_s": 1810 * NS, "steps": 3},
            "spans": {"dispatch": [1e-7] * 2}, "steps": 2,
            "programs": {"programs": 1, "compile_s": 2000 * NS}}


def test_the_view_is_cached_written_and_rendered(traced_process, trace_file):
    v = host_view.traced(traced_process)
    assert host_view.traced(traced_process) is v
    assert set(v["reader_s"]) == {"load_trace", "view"}
    assert v["trace_file"].startswith(".perfbench_out/toy_cell/trace/")
    written = trace_file[0] / ".perfbench_out" / "toy_cell" / "host_view.json"
    with open(written) as f:
        assert json.load(f)["idle_s"] == pytest.approx(730 * NS)
    text = host_view.render(v)
    for title in ("Idle time of the worst chip by class", "Host phases",
                  "The prefetcher's thread", "The longest gaps",
                  "Builds by namespace", "The costliest builds",
                  "Seconds the reader took"):
        assert title in text
    assert "| launched | 0.00034 |" in text
    read = manifest.load_module("layer_metrics",
                                "device.idle_launched_share").read
    assert read(traced_process) == pytest.approx(100 * 300 / 630)


def test_the_untraced_window_is_the_newest_steps(traced_process):
    """Two dispatches: steps 3 and 4 of the fixture.  Step 4's transfers are
    not in the list: none ends inside the window."""
    w = host_view.window(traced_process)
    assert {k: round(v / NS, 6) for k, v in w["phase_s"].items()} == {
        "prep": 90, "operands": 120, "launch": 40, "writeback": 20,
        "gate": 0}
    assert w["step_s"] == pytest.approx(270 * NS) and w["steps"] == 2
    assert (w["transfers"], w["bytes"], w["transfer_s"]) == (0, 0, 0.0)
    read = manifest.load_module("layer_metrics",
                                "step.operands_ms_per_step").read
    assert read(traced_process) == pytest.approx(1e3 * 120 * NS / 2)
    # the whole fixture is five steps: a window of five has no older step
    # to prove the ring still holds its first
    traced_process["spans"]["dispatch"] = [1e-7] * 5
    assert host_view.window(traced_process) is None
    assert read(traced_process) is None


class _Space:
    """A ``program_store`` namespace as far as ``programs.trace_s`` reads."""

    def __init__(self, **counters):
        self.__dict__.update(counters)


def test_trace_s_is_the_namespaces_counters(traced_process, monkeypatch):
    read = manifest.load_module("layer_metrics", "programs.trace_s").read
    assert read(traced_process) == pytest.approx(sum(
        ns.trace_seconds for ns in mx.program_store.NAMESPACES.values()))
    monkeypatch.setattr(mx.program_store, "NAMESPACES", {
        "a": _Space(compile_seconds=3.0, trace_seconds=1.25),
        "b": _Space(compile_seconds=1.0, trace_seconds=0.5)})
    assert read(traced_process) == 1.75
    # the parent of ISSUE 35's PR counts compile_seconds alone
    monkeypatch.setattr(mx.program_store, "NAMESPACES", {
        "a": _Space(compile_seconds=3.0)})
    assert read(traced_process) is None


@pytest.mark.parametrize("how", ["stale_trace", "no_trace_in_obs",
                                 "no_trace_on_disk", "spans_on_no_clock"])
def test_the_readers_read_nothing(traced_process, tmp_path, monkeypatch, how):
    if how == "stale_trace":          # another run's file is never read
        traced_process["trace"]["window_s"] = 1811 * NS
    elif how == "no_trace_in_obs":    # a rehearsal without a device plane
        traced_process["trace"] = None
    elif how == "no_trace_on_disk":
        monkeypatch.setattr(scope_view, "ROOT", str(tmp_path))
    else:                             # the parent: the files laid over it
        monkeypatch.setattr(
            mx.telemetry, "spans", lambda cat=None, **kw: [
                {"name": "train_step.step", "cat": "train_step",
                 "t0_us": 5, "dur_us": 7, "step": 1}])
        monkeypatch.setattr(mx.program_store, "NAMESPACES",
                            {"train_step": _Space(compile_seconds=3.0)})
    read = manifest.load_module("layer_metrics",
                                "device.idle_launched_share").read
    assert read(traced_process) is None
    if how == "spans_on_no_clock":
        for name in ROWS:
            assert manifest.load_module("layer_metrics", name).read(
                traced_process) is None, name


# ---------------------------------------------------------------------------
# the entries, and the readers on rehearsed cells
# ---------------------------------------------------------------------------
def _holds_the_row(per_layer, name):
    entry = next(m for m in per_layer if m["name"] == name)
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ROWS[name]
    # every cell trains through a compiled step: with no list of cells the
    # entry reaches each of them, those added later too
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves"}
    # the eight stand together, in the table's order, anywhere in the list:
    # entries appended after them leave them where they are
    names = [m["name"] for m in per_layer]
    first = names.index(next(iter(ROWS)))
    assert names[first:first + len(ROWS)] == list(ROWS)
    assert callable(manifest.load_module("layer_metrics", name).read)


@pytest.mark.parametrize("name", list(ROWS))
def test_a_new_entry_is_its_row_of_the_issue(name):
    _holds_the_row(manifest.load_benchmark()["per_layer"], name)
    # the two cells that no list of the eight ever named report them too
    for cell in ("olmo_hybrid_train_s8k", "granite_h_micro_train_s8k"):
        assert name in {m["name"] for m in manifest.resolve(cell).per_layer}


@pytest.mark.parametrize("name", list(ROWS))
def test_an_entry_appended_after_them_is_taken(name):
    """The next change to the benchmark appends its entries at the end of
    ``per_layer`` (an entry inserted in front of another is an edit of the
    one it displaces): the rows still hold with one more behind them."""
    per_layer = copy.deepcopy(manifest.load_benchmark()["per_layer"])
    per_layer.append({"name": "kernel.window_core_share", "unit": "%",
                      "better": "lower", "source": "device_trace",
                      "layer": "kernels", "moves": "mfu",
                      "workloads": ["olmo_hybrid_train_s8k"]})
    _holds_the_row(per_layer, name)
    # ... and an entry slipped in among them breaks the run
    names = [m["name"] for m in per_layer]
    per_layer.insert(names.index(name) + 1, per_layer.pop())
    if name != list(ROWS)[-1]:
        with pytest.raises(AssertionError):
            _holds_the_row(per_layer, name)


@pytest.mark.parametrize("cell", ["bert_base_train_s128",
                                  "bert_base_train_accum4",
                                  "resnet50_train_dp4"])
def test_the_readers_on_a_rehearsed_cell(cell, capsys):
    """Every reader but the device's gives a value on the CPU, and the phases
    are the step: they leave out only the call's own entry and exit."""
    rc = run.main(["--workload", cell, "--seed", "5", "--seconds", "0.5",
                   "--trace", "1", "--rehearse"],
                  t_start=time.perf_counter())
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    line = json.loads(out.out.strip().splitlines()[-1][len("REHEARSAL "):])
    assert line["correct"] is True, line["checks"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert "device.idle_launched_share" not in got     # no device plane
    for name in ROWS:
        if ROWS[name][2] != "device_trace":
            assert got[name] >= 0, name
    phases = sum(got[f"step.{p}_ms_per_step"]
                 for p in ("prep", "operands", "launch", "writeback"))
    assert 0 < phases <= got["step.host_ms_per_step"]
    assert got["step.launch_ms_per_step"] > 0
    assert got["input.transfer_ms_per_step"] > 0
    assert 0 <= got["input.slot_wait_share"] <= 100
    # the counters' total: set-up's in a run of the benchmark, which is a
    # process of its own; here it holds the worker's earlier builds too
    assert got["programs.trace_s"] == pytest.approx(sum(
        ns.trace_seconds for ns in mx.program_store.NAMESPACES.values()))
    assert 0 < got["programs.trace_s"] <= mx.program_store.compile_seconds()
