"""perfbench/trace_reduce.py on a small recorded trace: every expected value
is worked by hand from the intervals in data/two_chips.xplane.txt (an XSpace
in text form, so that the loader is under test too).  CPU only."""
import os

import pytest

from perfbench import trace_reduce as tr
from perfbench.spans import Spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NS = 1e-9


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "two_chips.xplane.txt")) as f:
        serialized = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("trace") / "two_chips.xplane.pb"
    path.write_bytes(serialized)
    events, start = tr.load_xplane(str(path))
    assert start == 1_790_000_000_000_000_000
    # the benchmark's host spans, taken on the wall clock
    spans = Spans()
    spans.records["dispatch"].append((start + 0, start + 8))
    spans.records["input_wait"].append((start + 340, start + 380))
    spans.records["loss_read"].append((start + 380, start + 400))
    return tr.reduce(events, steps=1, spans=spans.since(start))


def test_window_and_busy_union(reduced):
    # first start 0 (chip 0), last end 400 (chip 0's while)
    assert reduced["window_s"] == pytest.approx(400 * NS)
    chip0, chip1 = reduced["devices"]
    # chip 0: [0,180] u [200,400], the nested convolution adds nothing
    assert chip0["busy_s"] == pytest.approx(380 * NS)
    # chip 1: [10,350]
    assert chip1["busy_s"] == pytest.approx(340 * NS)
    assert reduced["busy_s"] == pytest.approx(360 * NS)      # mean of chips
    assert [d["module_runs"] for d in reduced["devices"]] == [1, 1]
    # the CUSTOM plane's 1000 ns event is no device operation
    assert [d["plane"] for d in reduced["devices"]] == ["/device:TPU:0",
                                                        "/device:TPU:1"]


def test_idle_share_per_chip(reduced):
    chip0, chip1 = reduced["devices"]
    assert chip0["idle_share"] == pytest.approx(1 - 380 / 400)
    assert chip1["idle_share"] == pytest.approx(1 - 340 / 400)


def test_category_self_times(reduced):
    chip0, chip1 = (d["category_s"] for d in reduced["devices"])
    want0 = {"matrix": 100 + 60,        # output fusion + the convolution
             "collective": 10 + 30,     # all-reduce-start + -done
             "other": 40 + (100 - 60),  # loop fusion + the while's own time
             "reduce": 60, "layout": 40}
    want1 = {"matrix": 110, "collective": 40, "other": 190,
             "reduce": 0, "layout": 0}
    for got, want in ((chip0, want0), (chip1, want1)):
        assert got == pytest.approx({k: v * NS for k, v in want.items()})
    assert sum(chip0.values()) == pytest.approx(380 * NS)    # = busy
    assert reduced["category_s"]["matrix"] == pytest.approx(135 * NS)


def test_collective_overlap(reduced):
    chip0, chip1 = reduced["devices"]
    # chip 0: in flight [100,180]; the loop fusion [110,150] hides 40 of it
    assert chip0["collective_s"] == pytest.approx(80 * NS)
    assert chip0["collective_exposed_s"] == pytest.approx(40 * NS)
    # chip 1: a synchronous all-reduce [120,160], nothing beside it
    assert chip1["collective_s"] == pytest.approx(40 * NS)
    assert chip1["collective_exposed_s"] == pytest.approx(40 * NS)


def test_idle_gaps_are_named_after_the_benchmarks_spans(reduced):
    # the most idle chip is chip 1: gaps [0,10] and [350,400].  dispatch
    # [0,8] covers most of the first; of the second input_wait [340,380]
    # covers 30 and loss_read [380,400] only 20
    assert reduced["idle_gaps"] == [["input_wait", pytest.approx(50 * NS)],
                                    ["dispatch", pytest.approx(10 * NS)]]


def test_top_operations(reduced):
    ops = dict(reduced["device_ops"])
    # per name, self time, mean over the two chips
    assert ops["fusion.1 [fusion kOutput]"] == pytest.approx(105 * NS)
    assert ops["fusion.9 [fusion kLoop]"] == pytest.approx(95 * NS)
    assert ops["while.1 [while]"] == pytest.approx(20 * NS)
    assert list(ops)[0] == "fusion.1 [fusion kOutput]"


def test_no_device_plane_reads_as_nothing():
    host_only = [tr.Event("/host:CPU", "python", "dot_general.1", 0.0, 5.0)]
    assert tr.reduce(host_only) is None


# names as a v5e trace gives them (my chip run, PR 22)
@pytest.mark.parametrize("text, want", [
    ("%convert_reduce_fusion = (f32[64]{0:T(128)S(1)}, f32[64]{0:T(128)S(1)}, "
     "bf16[64,32,32,64]{3,0,2,1:T(8,128)(2,1)S(1)}) fusion(f32[64,3,3,16]"
     "{0,3,2,1:T(8,128)} %w__c__.1, f32[64,32,32,16]{0,3,2,1:T(8,128)} %x.1), "
     "kind=kOutput, calls=%fused_computation.2", "matrix"),
    ("%copy-start.1 = (f32[64]{0:T(128)}, f32[64]{0:T(128)S(1)}, u32[]{:S(2)})"
     " copy-start(f32[64]{0:T(128)S(1)} %get-tuple-element.2)", "layout"),
    ("%subtract_reduce_fusion = f32[]{:T(128)} fusion(bf16[64,128]{1,0:T(8,128)"
     "(2,1)S(1)} %get-tuple-element.12), kind=kLoop, "
     "calls=%fused_computation.32", "reduce"),
    ("%custom-call = f32[65536,128]{1,0:T(8,128)S(1)} custom-call(f32[16384,128]"
     "{1,0:T(8,128)S(1)} %slice-done), custom_call_target=\"ConcatBitcast\"",
     "layout"),
    ("%slice-start = ((f32[65536,128]{1,0:T(8,128)}), f32[16384,128]{1,0:T(8,128)"
     "S(1)}, s32[]{:S(2)}) async-start(f32[65536,128]{1,0:T(8,128)} %w__d__.1), "
     "calls=%async_computation", "layout"),
    ("%fusion.8 = bf16[64,64,32,32]{1,0,3,2:T(8,128)(2,1)S(1)} fusion(bf16[64,32,"
     "32,64]{3,0,2,1:T(8,128)(2,1)S(1)} %get-tuple-element.10), kind=kLoop, "
     "calls=%fused_computation.18", "other"),
    # profile_step.classify read every "convert" as a convolution
    ("%convert_fusion.3 = bf16[8]{0} fusion(f32[8]{0} %p), kind=kLoop, "
     "calls=%fc", "other"),
    ("%all-gather-start = (f32[8]{0}, f32[32]{0}) all-gather-start(f32[8]{0} "
     "%p), dimensions={0}", "collective"),
    ("fusion.12", "other"),                      # not HLO text: name only
    ("conv1x1_bn_stats", "matrix"),
])
def test_categorise(text, want):
    assert tr.categorise(text) == want


def test_a_stated_category_wins():
    assert tr.categorise("%fusion.8 = f32[8]{0} fusion(f32[8]{0} %p), "
                         "kind=kLoop", stated="convolution fusion") == "matrix"
    assert tr.categorise("%x = f32[8]{0} fusion()", stated="data formatting") \
        == "layout"


def test_parse_hlo_skips_tuple_shapes_and_layouts():
    name, opcode, kind = tr.parse_hlo(
        "%reduce_fusion = (f32[64]{0:T(128)}, f32[64]{0:T(128)}) fusion(bf16"
        "[128,56,56,64]{3,0,2,1:T(8,128)(2,1)} %fusion.1), kind=kInput, "
        "calls=%fused_computation.3")
    assert (name, opcode, kind) == ("reduce_fusion", "fusion", "Input")


def test_interval_arithmetic():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)])
    assert merged == [(0, 3), (5, 9)]
    assert tr.measure(merged) == 7
    assert tr.intersect(merged, [(2, 6), (8, 20)]) == [(2, 3), (5, 6), (8, 9)]
    assert tr.complement(merged, (0, 10)) == [(3, 5), (9, 10)]


def test_a_gap_no_span_covers_says_so():
    ops = [tr.Event("/device:TPU:0", tr.OPS_LINE, "%a = f32[] add()", 0, 10),
           tr.Event("/device:TPU:0", tr.OPS_LINE, "%a = f32[] add()", 30, 40)]
    assert tr.reduce(ops)["idle_gaps"] == [["no span", pytest.approx(20e-9)]]


def test_spans_record_wall_time():
    spans = Spans()
    with spans("dispatch"):
        pass
    (name, start, end), = spans.since(0)
    assert name == "dispatch" and 0 < start <= end
    assert spans.seconds("dispatch") == [(end - start) / 1e9]
    assert spans.seconds("never") == []
