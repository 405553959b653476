"""The host half of a ``--trace 1`` run: the program's own host spans laid
over the device trace, and every idle gap of the most idle chip put down to
a host phase or to a program that was already launched.

What a reader author must know (beside ``scope_view.py``'s docstring):

- **The spans.**  ``mxnet_tpu.telemetry.spans()`` returns the program's span
  records; since ISSUE 35 each has ``t0_ns`` / ``t1_ns`` on ``time.time_ns``
  (the clock the device trace's ``profile_start_time`` is on), an ``id`` and
  the ``parent`` that caused it.  A compiled train step records
  ``train_step.step`` with the children ``train_step.prep``, ``.operands``,
  ``.launch`` (``args.program``: ``step``, ``grad`` or ``update``),
  ``.writeback`` and, under a loss scaler, ``.gate``; a build records
  ``program.build`` > ``program.trace`` + ``program.compile``; the
  prefetcher's thread ``input.transfer`` (``args.bytes``) and
  ``input.slot_wait`` (docs/OBSERVABILITY.md, "Host phases").  A program
  without them (the parent of that PR) gives every reader here ``None``.
- **Which launch a device run belongs to.**  The k-th run of a step program
  on a chip (an ``XLA Modules`` event named ``jit_mx_train_step__*``,
  ``jit_mx_accum_grad__*`` or ``jit_mx_accum_update__*``) belongs to the
  k-th ``train_step.launch`` span of that ``program`` after the trace's
  start: the driver's warm-up loop has read every loss before the trace
  begins, so nothing is in flight, and the traced loop reads every loss
  before the trace ends.  Where the trace shows FEWER runs than launches
  (the profiler lost a device event) the newest are matched to the newest
  and ``matched`` says ``tail``; a run that would start before its launch
  does, by more than ``CLOCK_SLACK_NS``, or more runs than launches, gives
  ``None``.  The slack is the error of laying the device trace over
  ``time.time_ns``: the first traced run, which the chip starts as soon as
  its launch hands it over, began 0.17 ms before to 1.04 ms after its
  ``launch`` span's start in five delta-rule and Granite runs on a v5e.
- **The classes of idle time.**  For a gap ``(a, b)`` of the worst chip's
  idle time, ended by a step program's run whose ``launch`` span ended at
  ``L``: ``(max(a, L), b)`` is ``launched`` (the host had handed the program
  over, the chip waited for something else: the batch's transfer, a peer
  chip); ``(a, min(b, L))`` is the host's, split by the phase span that
  covers it (``prep``, ``operands``, ``launch``, ``writeback``, ``gate``)
  and ``outside_step`` for what no phase covers (the user's loop: the
  benchmark's ``input_wait`` and ``loss_read``).  A gap a small program's
  run ends, or no run at all, is the host's whole.  The classes sum to the
  worst chip's idle time.
- **The refill is where the trace starts, not what the loop does.**  The
  driver's warm-up loop has read every loss, so the trace begins with
  nothing in flight and the chip idles until the first traced step's
  program starts: its ``operands`` and ``launch`` with no step ahead of
  them, 5-12 ms of a one-chip cell's 8-12 ms of traced idle where the steady
  gaps are 0.02 ms (my chip runs, PR 35).  Every gap that begins before
  the first step program's run does is ``refill`` (``refill_s``; a run's
  ``XLA Modules`` event begins a little before its first operation, where
  the gap ends): it stays in
  ``idle_class_s``, which sums to ``device.idle_share``'s idle, and is left
  out of ``launched_share``, numerator and denominator.
- **A ring that has lost what a reader needs gives ``None``**, never a mean
  over what is left: :func:`traced` wants a ``train_step`` record older than
  the trace's start, :func:`window` a ``train_step.step`` older than the
  window's first.

:func:`traced` returns the view below or ``None`` (no device trace in
``obs``, no raw trace on disk, a raw trace whose window is not
``obs['trace']['window_s']``, no spans); it is cached and written to
``.perfbench_out/<cell>/host_view.json``;

    python3 -m perfbench.host_view .perfbench_out/<cell>/host_view.json

prints it as tables.  :func:`window` is the untraced window: the newest
``len(obs['spans']['dispatch'])`` ``train_step.step`` spans with their
children, and the ``input`` spans that end inside them.

The view: ``window_s``, ``steps``, ``chips``, ``worst_plane``, ``idle_s``,
``idle_share``; ``idle_class_s`` (class -> seconds), ``refill_s`` and
``launched_share`` (% of ``idle_s`` less ``refill_s``); ``matched`` and
``runs`` / ``launches`` (by program); ``gaps`` (the longest: start, end, the
module that ended it, whether it is ``refill``, seconds by class, the
``input.transfer`` spans that overlap it with their bytes);
``steps_traced`` (per ``train_step.step`` span of the traced window: its
host milliseconds and each phase's) and ``phase_ms_per_step`` (their mean
over ``steps``); ``transfers`` (count, bytes, seconds, the thread's
slot-wait seconds in the traced window); ``builds`` (the costliest
``program.build`` spans the ring holds: module, namespace, whether inside a
step, seconds of build, trace and compile, ``cache``, ``retrieval_s``) and
``builds_by_namespace`` (all of them: count, the three sums, disk hits and
misses); ``reader_s``, ``trace_file``.
"""
from __future__ import annotations

import bisect
import json
import os
import sys
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

from perfbench import scope_view
from perfbench import trace_reduce as tr

STEP_PROGRAMS = (("jit_mx_train_step__", "step"),
                 ("jit_mx_accum_grad__", "grad"),
                 ("jit_mx_accum_update__", "update"))
PHASES = ("prep", "operands", "launch", "writeback", "gate")
CLASSES = ("launched",) + PHASES + ("outside_step",)
_EVERYTHING = (float("-inf"), float("inf"))
CLOCK_SLACK_NS = 2_000_000
_CACHE: Dict[Tuple[str, float], Optional[Dict[str, Any]]] = {}


def _program_of(module_event_name: str) -> Optional[str]:
    return next((kind for prefix, kind in STEP_PROGRAMS
                 if module_event_name.startswith(prefix)), None)


def _phase_cover(spans) -> Dict[str, List[tr.Interval]]:
    """Each phase's merged intervals, made disjoint: a ``gate`` read inside
    ``prep`` (``TrainStep.drain``) is the gate's."""
    by_phase = defaultdict(list)
    for s in spans:
        cat, _, phase = s["name"].partition(".")
        if cat == "train_step" and phase in PHASES:
            by_phase[phase].append((s["t0"], s["t1"]))
    cover = {p: tr.union(by_phase[p]) for p in PHASES}
    not_gate = tr.complement(cover["gate"], _EVERYTHING)
    return {p: c if p == "gate" else tr.intersect(c, not_gate)
            for p, c in cover.items()}


def _match(runs, launches, window_end):
    """``(launch of each run, how)`` by order (module docstring), or
    ``(None, None)``."""
    launches = [l for l in launches if 0 <= l["t0"] <= window_end]
    if len(launches) < len(runs):
        return None, None
    how = "one_to_one" if len(launches) == len(runs) else "tail"
    mine = launches[len(launches) - len(runs):]
    if any(run.start_ns < l["t0"] - CLOCK_SLACK_NS
           for run, l in zip(runs, mine)):
        return None, None
    return mine, how


def view(events: Sequence[tr.Event], start_ns: int,
         spans: Sequence[Dict[str, Any]], steps: Optional[int] = None,
         top: int = 12) -> Optional[Dict[str, Any]]:
    """The module docstring's view of one traced window.  ``events`` and
    ``start_ns`` as ``trace_reduce.load_xplane`` returns them, ``spans`` as
    ``mxnet_tpu.telemetry.spans()`` does."""
    planes: Dict[str, Dict[str, List[tr.Event]]] = defaultdict(
        lambda: defaultdict(list))
    for ev in events:
        if tr.is_device_plane(ev.plane):
            planes[ev.plane][ev.line].append(ev)
    planes = {p: l for p, l in planes.items() if l.get(tr.OPS_LINE)}
    if not planes or not spans or "t0_ns" not in spans[0]:
        return None
    spans = [dict(s, t0=s["t0_ns"] - start_ns, t1=s["t1_ns"] - start_ns)
             for s in spans]
    step_cat = [s for s in spans if s["cat"] == "train_step"]
    # the ring must reach back before the trace: else its first traced
    # step may be among what it dropped
    if not step_cat or min(s["t0"] for s in step_cat) >= 0:
        return None

    window = (min(e.start_ns for l in planes.values()
                  for e in l[tr.OPS_LINE]),
              max(e.end_ns for l in planes.values() for e in l[tr.OPS_LINE]))
    window_ns = window[1] - window[0]
    worst, busy = None, None
    for plane in sorted(planes):         # trace_reduce's worst chip
        b = tr.union((e.start_ns, e.end_ns)
                     for e in planes[plane][tr.OPS_LINE])
        if busy is None or tr.measure(b) < tr.measure(busy):
            worst, busy = plane, b
    gaps = tr.complement(busy, window)
    modules = sorted(planes[worst].get(tr.MODULES_LINE, ()),
                     key=lambda e: e.start_ns)

    launch_of: Dict[int, Dict[str, Any]] = {}      # index in modules
    runs_n, launches_n, how_all = {}, {}, set()
    for _prefix, kind in STEP_PROGRAMS:
        runs = [(i, m) for i, m in enumerate(modules)
                if _program_of(m.name) == kind]
        launches = sorted((s for s in step_cat
                           if s["name"] == "train_step.launch"
                           and (s.get("args") or {}).get("program") == kind),
                          key=lambda s: s["t0"])
        mine, how = _match([m for _i, m in runs], launches, window[1])
        if mine is None:
            return None
        runs_n[kind], launches_n[kind] = len(runs), sum(
            1 for l in launches if 0 <= l["t0"] <= window[1])
        if runs:
            how_all.add(how)
        launch_of.update({i: l for (i, _m), l in zip(runs, mine)})
    if not launch_of:
        return None                      # no step program ran in the trace

    cover = _phase_cover(step_cat)
    transfers = sorted((s for s in spans if s["name"] == "input.transfer"),
                       key=lambda s: s["t0"])
    starts = [m.start_ns for m in modules]
    first_run = min(modules[i].start_ns for i in launch_of)
    class_ns = dict.fromkeys(CLASSES, 0.0)
    refill_ns = steady_launched_ns = 0.0
    rows = []
    for a, b in gaps:
        # the run that ends the gap: the one running at b
        i = bisect.bisect_right(starts, b) - 1
        ender = modules[i] if i >= 0 and b < modules[i].end_ns else None
        by = dict.fromkeys(CLASSES, 0.0)
        host_end = b
        if ender is not None and i in launch_of:
            launched_from = max(a, min(b, launch_of[i]["t1"]))
            by["launched"] = b - launched_from
            host_end = launched_from
        if host_end > a:
            for phase in PHASES:
                by[phase] = tr.measure(tr.intersect(cover[phase],
                                                    [(a, host_end)]))
            by["outside_step"] = (host_end - a) - sum(by[p] for p in PHASES)
        for k, v in by.items():
            class_ns[k] += v
        refill = a < first_run
        if refill:
            refill_ns += b - a
        else:
            steady_launched_ns += by["launched"]
        rows.append({
            "start_s": a / 1e9, "end_s": b / 1e9,
            "ended_by": scope_view._module_name(ender.name)
            if ender is not None else None,
            "refill": refill,
            "class_s": {k: v / 1e9 for k, v in by.items() if v},
            "transfers": [{"start_s": t["t0"] / 1e9, "end_s": t["t1"] / 1e9,
                           "bytes": (t.get("args") or {}).get("bytes")}
                          for t in transfers if t["t0"] < b and t["t1"] > a],
        })
    idle_ns = tr.measure(gaps)

    traced_steps = [s for s in step_cat if s["name"] == "train_step.step"
                    and 0 <= s["t0"] <= window[1]]
    per_step = []
    for s in traced_steps:
        ms = {"step": s["step"], "host_ms": (s["t1"] - s["t0"]) / 1e6}
        for kid in step_cat:
            if kid["parent"] == s["id"]:
                phase = kid["name"].partition(".")[2]
                ms[phase] = ms.get(phase, 0.0) + (kid["t1"] - kid["t0"]) / 1e6
        per_step.append(ms)
    k = steps or len(per_step) or 1
    in_window = [s for s in spans if s["cat"] == "input"
                 and 0 <= s["t1"] and s["t0"] <= window[1]]
    return {
        "window_s": window_ns / 1e9,
        "steps": steps,
        "chips": len(planes),
        "worst_plane": worst,
        "idle_s": idle_ns / 1e9,
        "idle_share": idle_ns / window_ns,
        "idle_class_s": {c: v / 1e9 for c, v in class_ns.items()},
        "refill_s": refill_ns / 1e9,
        "launched_share": 100.0 * steady_launched_ns / (idle_ns - refill_ns)
        if idle_ns > refill_ns else None,
        "matched": "tail" if "tail" in how_all else "one_to_one",
        "runs": runs_n,
        "launches": launches_n,
        "gap_count": len(gaps),
        "gaps": sorted(rows, key=lambda r: r["start_s"] - r["end_s"])[:top],
        "steps_traced": per_step,
        "phase_ms_per_step": {
            p: sum(s.get(p, 0.0) for s in per_step) / k
            for p in ("host_ms",) + PHASES},
        "transfers": {
            "count": sum(s["name"] == "input.transfer" for s in in_window),
            "bytes": sum((s.get("args") or {}).get("bytes") or 0
                         for s in in_window),
            "seconds": sum(s["t1"] - s["t0"] for s in in_window
                           if s["name"] == "input.transfer") / 1e9,
            "slot_wait_s": sum(s["t1"] - s["t0"] for s in in_window
                               if s["name"] == "input.slot_wait") / 1e9},
        **_builds_summary(builds(spans), top),
    }


def builds(spans) -> List[Dict[str, Any]]:
    """Every ``program.build`` among ``spans`` with its trace and compile
    seconds, oldest first."""
    by_parent = defaultdict(dict)
    for s in spans:
        if s["name"] in ("program.trace", "program.compile"):
            by_parent[s["parent"]][s["name"]] = s
    launches = {s["id"] for s in spans if s["name"] == "train_step.launch"}
    out = []
    for s in spans:
        if s["name"] != "program.build":
            continue
        kids, args = by_parent[s["id"]], s.get("args") or {}

        def seconds(name):
            kid = kids.get(name)
            return (kid["t1_ns"] - kid["t0_ns"]) / 1e9 if kid else None

        compile_args = (kids.get("program.compile") or {}).get("args") or {}
        out.append({
            "module": args.get("module"), "namespace": args.get("namespace"),
            "in_step": s["parent"] in launches,
            "build_s": (s["t1_ns"] - s["t0_ns"]) / 1e9,
            "trace_s": seconds("program.trace"),
            "compile_s": seconds("program.compile"),
            "cache": compile_args.get("cache"),
            "retrieval_s": compile_args.get("retrieval_s")})
    return out


def _builds_summary(all_builds, top) -> Dict[str, Any]:
    by_ns: Dict[str, Dict[str, Any]] = defaultdict(
        lambda: {"builds": 0, "build_s": 0.0, "trace_s": 0.0,
                 "compile_s": 0.0, "hit": 0, "miss": 0, "off": 0})
    for b in all_builds:
        row = by_ns[b["namespace"]]
        row["builds"] += 1
        for key in ("build_s", "trace_s", "compile_s"):
            row[key] += b[key] or 0.0
        if b["cache"] in row:
            row[b["cache"]] += 1
    return {"builds": sorted(all_builds, key=lambda b: -b["build_s"])[:top],
            "builds_by_namespace": dict(by_ns)}


# ---------------------------------------------------------------------------
# this process: the traced window, the untraced window
# ---------------------------------------------------------------------------
def _program_spans(cat=None) -> Optional[List[Dict[str, Any]]]:
    """The program's span records, or ``None`` where they lie on no clock a
    trace can be laid over (the parent of ISSUE 35's PR)."""
    import mxnet_tpu as mx

    spans = mx.telemetry.spans(cat=cat) if cat else mx.telemetry.spans()
    return spans if spans and "t0_ns" in spans[0] else None


def traced(obs) -> Optional[Dict[str, Any]]:
    """:func:`view` of the trace the driver of this process just wrote, or
    ``None`` (module docstring)."""
    if not obs.get("trace"):
        return None
    path = scope_view._newest_trace()
    spans = _program_spans()
    if path is None or spans is None:
        return None
    key = (path, os.path.getmtime(path))
    if key in _CACHE:
        return _CACHE[key]
    t0 = time.perf_counter()
    events, start_ns = tr.load_xplane(path)
    t_load = time.perf_counter()
    out = view(events, start_ns, spans, steps=obs["trace"]["steps"])
    if out is not None and abs(out["window_s"] - obs["trace"]["window_s"]) \
            > 1e-9 * max(1.0, obs["trace"]["window_s"]):
        out = None                      # another run's trace
    if out is not None:
        out["trace_file"] = os.path.relpath(path, scope_view.ROOT)
        out["reader_s"] = {"load_trace": t_load - t0,
                           "view": time.perf_counter() - t_load}
        cell_dir = path.split(os.sep + "trace" + os.sep)[0]
        with open(os.path.join(cell_dir, "host_view.json"), "w") as f:
            json.dump(out, f, indent=1)
    _CACHE[key] = out
    return out


def window(obs) -> Optional[Dict[str, Any]]:
    """The untraced window by the program's spans: ``steps`` (the driver's
    count: a window of an accumulation cell is a step), ``phase_s`` (seconds
    in each phase over the window's ``train_step.step`` spans), ``step_s``
    (those spans' own seconds), and of the prefetcher's thread
    ``transfer_s``, ``slot_wait_s``, ``transfers``, ``bytes`` (``None``
    where the ``input`` ring does not reach back to the window's start)."""
    calls = len(obs["spans"].get("dispatch") or ())
    spans = _program_spans("train_step")
    if not calls or not obs["steps"] or spans is None:
        return None
    steps = [s for s in spans if s["name"] == "train_step.step"]
    if len(steps) <= calls:
        return None                     # the ring has lost the first step
    mine = {s["id"]: s for s in steps[-calls:]}
    phase_s = dict.fromkeys(PHASES, 0.0)
    for s in spans:
        phase = s["name"].partition(".")[2]
        if s["parent"] in mine and phase in phase_s:
            phase_s[phase] += (s["t1_ns"] - s["t0_ns"]) / 1e9
    first, last = steps[-calls]["t0_ns"], steps[-1]["t1_ns"]
    out = {"steps": obs["steps"], "phase_s": phase_s,
           "step_s": sum(s["t1_ns"] - s["t0_ns"]
                         for s in mine.values()) / 1e9,
           "transfer_s": None, "slot_wait_s": None, "transfers": None,
           "bytes": None}
    inputs = _program_spans("input")
    if inputs and inputs[0]["t1_ns"] < first:
        inside = [s for s in inputs if first <= s["t1_ns"] <= last]
        moved = [s for s in inside if s["name"] == "input.transfer"]
        out.update(
            transfer_s=sum(s["t1_ns"] - s["t0_ns"] for s in moved) / 1e9,
            slot_wait_s=sum(s["t1_ns"] - s["t0_ns"] for s in inside
                            if s["name"] == "input.slot_wait") / 1e9,
            transfers=len(moved),
            bytes=sum((s.get("args") or {}).get("bytes") or 0
                      for s in moved))
    return out


def phase_ms(obs, phase: str) -> Optional[float]:
    """Milliseconds a step in ``phase``, the mean over the untraced window."""
    w = window(obs)
    return 1e3 * w["phase_s"][phase] / w["steps"] if w else None


# ---------------------------------------------------------------------------
# python3 -m perfbench.host_view <host_view.json>
# ---------------------------------------------------------------------------
def render(v: Dict[str, Any]) -> str:
    table = scope_view._table
    k = v["steps"] or 1
    idle = v["idle_s"] or 1.0
    out = [f"{v.get('trace_file', '')}: window {v['window_s']:.4f} s, "
           f"{v['steps']} steps, {v['chips']} chip(s); {v['worst_plane']} "
           f"idle {1e3 * v['idle_s']:.3f} ms ({100 * v['idle_share']:.3f}%) "
           f"in {v['gap_count']} gaps, {1e3 * v['refill_s']:.3f} ms of it "
           f"the refill before the first step program, "
           f"{v['launched_share'] or 0.0:.2f}% of the rest launched; "
           f"runs {v['runs']} matched "
           f"{v['matched']} to launches {v['launches']}"]
    out.append(table(
        "Idle time of the worst chip by class",
        ("class", "ms", "ms a step", "% of idle"),
        [(c, 1e3 * s, 1e3 * s / k, 100.0 * s / idle)
         for c, s in v["idle_class_s"].items()]))
    out.append(table(
        "Host phases, ms a step (mean over the traced steps)",
        tuple(v["phase_ms_per_step"]),
        [tuple(v["phase_ms_per_step"].values())]))
    t = v["transfers"]
    out.append(table(
        "The prefetcher's thread in the traced window",
        ("transfers", "MB", "transfer ms", "MB a transfer", "ms a transfer",
         "slot-wait ms"),
        [(t["count"], t["bytes"] / 1e6, 1e3 * t["seconds"],
          t["bytes"] / 1e6 / max(t["count"], 1),
          1e3 * t["seconds"] / max(t["count"], 1), 1e3 * t["slot_wait_s"])]))
    out.append(table(
        "The longest gaps", ("start ms", "ms", "ended by", "refill",
                             "by class (ms)", "transfers under it (ms, MB)"),
        [(1e3 * g["start_s"], 1e3 * (g["end_s"] - g["start_s"]),
          g["ended_by"], g["refill"],
          " ".join(f"{c}={1e3 * s:.3f}" for c, s in g["class_s"].items()),
          " ".join(f"({1e3 * (x['end_s'] - x['start_s']):.2f}, "
                   f"{(x['bytes'] or 0) / 1e6:.1f})"
                   for x in g["transfers"])) for g in v["gaps"]]))
    out.append(table(
        "Builds by namespace", ("namespace", "builds", "build s", "trace s",
                                "compile or load s", "hit", "miss", "off"),
        [(ns, r["builds"], r["build_s"], r["trace_s"], r["compile_s"],
          r["hit"], r["miss"], r["off"])
         for ns, r in v["builds_by_namespace"].items()]))
    out.append(table(
        "The costliest builds", ("module", "namespace", "in a step",
                                  "build s", "trace s", "compile s", "cache",
                                  "retrieval s"),
        [(b["module"], b["namespace"], b["in_step"], b["build_s"],
          b["trace_s"] or 0.0, b["compile_s"] or 0.0, b["cache"],
          b["retrieval_s"] or 0.0) for b in v["builds"]]))
    if "reader_s" in v:
        out.append(table("Seconds the reader took", tuple(v["reader_s"]),
                         [tuple(v["reader_s"].values())]))
    return "\n".join(out)


if __name__ == "__main__":
    with open(sys.argv[1]) as _f:
        print(render(json.load(_f)))
