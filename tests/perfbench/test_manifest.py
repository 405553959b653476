"""BENCHMARK.json against the files it names, and every cell's rehearsal:
the whole loop at toy sizes on the CPU, in this process, on the suite's
persistent compile cache."""
import json
import os
import re
import time

import pytest

from perfbench import manifest, run

BENCH = manifest.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_the_file_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    for part in BENCH["command"]:            # names no file outside paths
        assert not part.startswith("/") and ".." not in part
        if os.path.exists(os.path.join(manifest.ROOT, part)):
            assert any(part.startswith(p + "/") for p in BENCH["paths"])


def test_every_name_is_plain_and_used_once():
    groups = [BENCH["configs"], BENCH["workloads"],
              BENCH["end_to_end"] + BENCH["per_layer"]]
    for group in groups:
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        assert all(re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}", n)
                   for n in names)
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert len(e["why"]) <= 200, e["name"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_bounds_and_sources():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    for m in BENCH["per_layer"]:
        assert "bound" not in m and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        # PERF.md section 3 names its layers the same way: no spaces
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", m["layer"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_a_cell_names_exists(cell):
    c = manifest.resolve(cell)
    for fn in ("build", "ops_per_sample", "make_pool", "check_batch",
               "reference"):
        assert callable(getattr(c.config_module, fn))
    assert callable(c.driver.run)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "samples_per_s"}
    assert c.per_layer
    for m in c.per_layer:
        assert callable(manifest.load_module("layer_metrics", m["name"]).read)
    entry = next(e for e in BENCH["configs"] if e["name"] == c.config)
    assert entry["file"].startswith("perfbench/configs/")
    assert c.sizes["source"] == entry["source"]
    assert c.sizes["reduced"] == entry["reduced"]
    # the toy sizes never leak into the real cell
    toy = manifest.resolve(cell, rehearse=True)
    assert toy.mix["batch_per_chip"] < c.mix["batch_per_chip"]


def test_an_unknown_name_is_refused():
    with pytest.raises(manifest.ManifestError):
        manifest.resolve("no_such_cell")
    with pytest.raises(manifest.ManifestError):
        manifest.resolve("../resnet50_train")
    with pytest.raises(manifest.ManifestError):
        manifest.load_module("layer_metrics", "no.such.metric")


def test_an_unknown_device_kind_raises():
    assert manifest.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(manifest.ManifestError, match="not in perfbench/peaks"):
        manifest.peak_for("cpu")
    with pytest.raises(manifest.ManifestError):
        manifest.peak_for("TPU v9")


def test_without_a_tpu_a_cell_exits_nonzero_and_prints_no_result(capsys):
    assert run.main(["--workload", CELLS[0], "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def _rehearse(capsys, cell, trace):
    rc = run.main(["--workload", cell, "--seed", "3", "--seconds", "1",
                   "--trace", str(trace), "--rehearse"],
                  t_start=time.perf_counter())
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    lines = out.out.strip().splitlines()
    assert lines and all(l.startswith("REHEARSAL ") for l in lines)
    assert all(l.startswith("REHEARSAL ") for l in out.err.splitlines()
               if "[perfbench]" in l)
    return json.loads(lines[-1][len("REHEARSAL "):])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_the_whole_loop(cell, capsys):
    """One-layer BERT, 32-pixel ResNet, four of the suite's eight virtual
    devices for the dp4 cell."""
    line = _rehearse(capsys, cell, trace=0)
    assert set(line) >= LAST_LINE_KEYS
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 8
    want = {m["name"]: m["unit"] for m in manifest.resolve(cell).end_to_end}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"


def test_rehearsal_of_a_traced_run_reads_the_per_layer_metrics(capsys):
    cell = "bert_base_train_s128"
    line = _rehearse(capsys, cell, trace=1)
    assert set(line) >= LAST_LINE_KEYS and line["correct"] is True
    got = line["metrics"]
    assert got["step.dispatches_per_step"]["value"] == 1.0
    assert got["mesh.reshards_in_window"]["value"] == 0
    assert got["mesh.hbm_imbalance"]["value"] == 1.0
    assert got["step.host_ms_per_step"]["value"] > 0
    # the CPU has no device plane: a reader that finds nothing to read
    # returns nothing, and the metric is left out of the line
    per_layer = {m["name"]: m for m in manifest.resolve(cell).per_layer}
    missing = set(per_layer) - set(got)
    assert missing and all(per_layer[m]["source"] == "device_trace"
                           for m in missing)
    assert set(got) <= set(per_layer)
