"""Fleet telemetry aggregation (ISSUE 15).

Covers: (1) the flight recorder's atomic per-process shards —
pid/rank-stamped names, write-then-rename (no torn finals, no litter),
meta header with counter kinds, snapshot record last; (2)
``telemetry.merge``: cumulative counters sum across shards, gauges stay
per-process, events/spans come back process-stamped, torn shards and
``*.tmp`` litter are skipped not fatal; (3) the merged chrome trace:
one lane per process plus cross-process flow linking by trace_id; (4)
the ``MXNET_TELEMETRY_MAX_MB`` oldest-shard rotation (counted in
``telemetry.shards_rotated``); (5) the ``python -m mxnet_tpu.telemetry``
CLI (report/trace/merge) and ``tools/telemetry_merge.py``.
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mxnet_tpu import telemetry  # noqa: E402

import tools.telemetry_merge as merge_tool  # noqa: E402


# ---------------------------------------------------------------------------
# shards
# ---------------------------------------------------------------------------

def test_shard_atomic_write_naming_and_layout(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_DIR", str(tmp_path))
    telemetry.counter("test.fleet.alpha", "x").inc(3)
    telemetry.event("shed", "test.fleet.shard", reason="hello")
    path = telemetry.flush()
    assert os.path.basename(path) == \
        f"telemetry-r0-p{os.getpid()}.jsonl"
    # atomic: no tmp litter survives a completed flush
    assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert lines[0]["kind"] == "meta"
    assert lines[0]["pid"] == os.getpid()
    assert lines[0]["counter_kinds"]["test.fleet.alpha"] == "cumulative"
    assert lines[-1]["kind"] == "snapshot"
    assert lines[-1]["counters"]["test.fleet.alpha"] >= 3
    assert any(l.get("name") == "test.fleet.shard" for l in lines)
    # a re-flush REWRITES (meta+snapshot regenerated, data kept once)
    telemetry.flush()
    lines2 = [json.loads(l) for l in open(path) if l.strip()]
    assert sum(1 for l in lines2 if l.get("kind") == "meta") == 1
    assert sum(1 for l in lines2 if l.get("kind") == "snapshot") == 1
    assert sum(1 for l in lines2
               if l.get("name") == "test.fleet.shard") == 1


def _fake_shard(d, rank, pid, counters, kinds=None, events=(),
                spans=()):
    path = os.path.join(d, f"telemetry-r{rank}-p{pid}.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "meta", "pid": pid, "rank": rank,
                            "counter_kinds": kinds or {}}) + "\n")
        for ev in events:
            f.write(json.dumps(ev) + "\n")
        for sp in spans:
            f.write(json.dumps({"kind": "span", **sp}) + "\n")
        f.write(json.dumps({"kind": "snapshot", "counters": counters})
                + "\n")
    return path


def test_merge_sums_cumulative_keeps_gauges_per_process(tmp_path):
    kinds = {"a.total": "cumulative", "a.depth": "gauge",
             "a.secs": "time"}
    _fake_shard(str(tmp_path), 0, 100,
                {"a.total": 5, "a.depth": 2, "a.secs": 1.5}, kinds,
                events=[{"kind": "shed", "name": "m", "seq": 1,
                         "t_us": 10, "trace_id": "aa-1"}],
                spans=[{"name": "decode.step", "cat": "decode",
                        "t0_us": 5, "dur_us": 3, "seq": 1,
                        "trace_id": "aa-1", "thread": 7}])
    _fake_shard(str(tmp_path), 1, 200,
                {"a.total": 7, "a.depth": 9, "a.secs": 0.5}, kinds,
                spans=[{"name": "decode.step", "cat": "decode",
                        "t0_us": 8, "dur_us": 2, "seq": 1,
                        "trace_id": "aa-1", "thread": 9}])
    m = telemetry.merge(str(tmp_path))
    assert len(m["shards"]) == 2
    assert m["counters"]["a.total"] == 12          # summed
    assert m["counters"]["a.secs"] == 2.0          # time sums too
    assert "a.depth" not in m["counters"]          # gauges do NOT sum
    assert sorted(m["gauges"]["a.depth"].values()) == [2, 9]
    assert [e["pid"] for e in m["events"]] == [100]
    assert sorted(s["pid"] for s in m["spans"]) == [100, 200]
    # the merged chrome trace: one lane per process + one cross-process
    # flow for the shared trace_id
    ct = telemetry.merge_chrome_trace(str(tmp_path), m)
    names = [e for e in ct["traceEvents"] if e["ph"] == "M"]
    assert len(names) == 2
    flows = [e for e in ct["traceEvents"] if e.get("cat") == "flow"]
    assert [f["ph"] for f in flows] == ["s", "t"]   # linked as ONE flow
    assert len({f["id"] for f in flows}) == 1
    assert len({f["pid"] for f in flows}) == 2      # across processes


def test_merge_skips_torn_and_tmp_files(tmp_path):
    _fake_shard(str(tmp_path), 0, 1, {"a.total": 1},
                {"a.total": "cumulative"})
    # a SIGKILLed child's torn final line + an in-flight tmp file
    with open(os.path.join(tmp_path, "telemetry-r0-p2.jsonl"), "w") as f:
        f.write(json.dumps({"kind": "meta", "pid": 2, "rank": 0,
                            "counter_kinds": {}}) + "\n")
        f.write('{"kind": "snapshot", "counters": {"a.to')   # torn
    with open(os.path.join(tmp_path,
                           "telemetry-r0-p3.jsonl.tmp.3"), "w") as f:
        f.write("garbage that is not json\n")
    m = telemetry.merge(str(tmp_path))
    assert len(m["shards"]) == 2                    # tmp file ignored
    assert m["skipped_lines"] == 1                  # torn line skipped
    assert m["counters"]["a.total"] == 1            # good shard intact


def test_rotation_deletes_oldest_shards(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_TELEMETRY_MAX_MB", "0.0005")   # ~500 B
    old = []
    for i in range(3):
        p = _fake_shard(str(tmp_path), 9, 1000 + i,
                        {"a.total": 1}, {"a.total": "cumulative"},
                        events=[{"kind": "shed", "name": "pad",
                                 "seq": j, "t_us": j,
                                 "reason": "x" * 64}
                                for j in range(20)])
        past = time.time() - 3600 + i
        os.utime(p, (past, past))
        old.append(p)
    rotated0 = telemetry.get("telemetry.shards_rotated").value
    own = telemetry.flush()
    assert os.path.exists(own)                      # never its own
    survivors = [f for f in os.listdir(tmp_path)
                 if f.endswith(".jsonl")]
    assert os.path.basename(own) in survivors
    assert len(survivors) < 4                       # oldest rotated out
    removed = 4 - len(survivors)
    assert telemetry.get("telemetry.shards_rotated").value \
        == rotated0 + removed
    # oldest-first: the newest fake shard outlives the oldest
    if len(survivors) > 1:
        assert os.path.basename(old[0]) not in survivors


# ---------------------------------------------------------------------------
# CLI + merge tool
# ---------------------------------------------------------------------------

def test_cli_report_trace_merge(tmp_path):
    d = tmp_path / "shards"
    d.mkdir()
    _fake_shard(str(d), 0, 11, {"a.total": 4}, {"a.total": "cumulative"},
                events=[{"kind": "admit", "name": "eng", "seq": 1,
                         "t_us": 1, "trace_id": "b-1"},
                        {"kind": "retire", "name": "eng", "seq": 2,
                         "t_us": 9, "trace_id": "b-1"}])
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.telemetry", "merge", str(d),
         "--json", "--chrome", str(tmp_path / "chrome.json")],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-1500:]
    merged = json.loads(r.stdout)
    assert merged["counters"]["a.total"] == 4
    chrome = json.load(open(tmp_path / "chrome.json"))
    assert "traceEvents" in chrome
    r = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.telemetry", "report",
         "--dir", str(d)],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    assert r.returncode == 0 and "a.total" in r.stdout
    r = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.telemetry", "trace", "b-1",
         "--dir", str(d)],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-1500:]
    tr = json.loads(r.stdout)
    assert [e["kind"] for e in tr["records"]] == ["admit", "retire"]


def test_merge_trace_in_process_smoke(tmp_path):
    """Tier-1 smoke for the slow CLI test above: the same shard fixture
    folded through the library entry points the CLI wraps — merge,
    chrome export, and per-trace stitch — without subprocesses."""
    from mxnet_tpu import telemetry as T
    d = tmp_path / "shards"
    d.mkdir()
    _fake_shard(str(d), 0, 11, {"a.total": 4}, {"a.total": "cumulative"},
                events=[{"kind": "admit", "name": "eng", "seq": 1,
                         "t_us": 1, "trace_id": "b-1"},
                        {"kind": "retire", "name": "eng", "seq": 2,
                         "t_us": 9, "trace_id": "b-1"}])
    merged = T.merge(str(d))
    assert merged["counters"]["a.total"] == 4
    chrome = T.merge_chrome_trace(str(d), merged)
    assert "traceEvents" in chrome
    tr = T._trace_from_merge(merged, "b-1")
    assert [e["kind"] for e in tr["records"]] == ["admit", "retire"]


def test_telemetry_merge_tool(tmp_path):
    d = tmp_path / "shards"
    d.mkdir()
    _fake_shard(str(d), 0, 1, {"a.total": 2}, {"a.total": "cumulative"})
    out = tmp_path / "merged.json"
    assert merge_tool.main([str(d), "--out", str(out)]) == 0
    assert json.load(open(out))["counters"]["a.total"] == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert merge_tool.main([str(empty)]) == 1

