"""From a profiler trace to numbers: the one reduction every PR is read by.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (nothing but jax), or the same events as plain
tuples (the tests' recorded list).  What a v5e trace looks like (my chip
run, PR 22, jax 0.9.0): one plane ``/device:TPU:<n>`` per chip with the
lines ``Steps``, ``XLA Modules``, ``XLA Ops`` (what the core executed; the
event's name is the instruction's full HLO text, it states no category) and
``Async XLA Ops`` (start-to-done spans of asynchronous copies and
collectives); host threads are lines of ``/host:CPU``; the plane ``Task
Environment`` states ``profile_start_time``, the wall time (ns) that every
event's ``start_ns`` counts from.

Definitions:

- window      first start to last end of any device operation, all chips
- busy        union of the ``XLA Ops`` intervals of one chip
- idle share  1 - busy / window, per chip
- category    self time (children of a ``while``/``conditional`` are not
              counted twice) of each operation, by :func:`categorise`
- collective  union of collective operations, synchronous or in flight;
  exposed     the part of it during which no other operation runs there
- idle gaps   the complement of busy on the most idle chip, each gap named
              after the benchmark's host span that covers most of it
"""
from __future__ import annotations

import re
from collections import defaultdict
from functools import lru_cache
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
CATEGORIES = ("matrix", "reduce", "layout", "collective", "other")

Interval = Tuple[float, float]


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float
    category: Optional[str] = None     # as the trace states it, if it does


def load_xplane(path: str) -> Tuple[List[Event], int]:
    """Every event of every line of an ``.xplane.pb``, and the wall time in
    ns that their ``start_ns`` count from."""
    from jax.profiler import ProfileData

    events, start = [], None
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        for key, value in plane.stats:
            if key == "profile_start_time":
                start = int(value)
        for line in plane.lines:
            for e in line.events:
                stated = None
                if device and line.name == OPS_LINE:
                    stated = next((str(v) for k, v in e.stats
                                   if k == "hlo_category"), None)
                events.append(Event(plane.name, line.name, e.name,
                                    e.start_ns, e.start_ns + e.duration_ns,
                                    stated))
    if start is None:
        raise ValueError(f"{path} states no profile_start_time")
    return events, start


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name


# ---------------------------------------------------------------------------
# operations: name, opcode, category
# ---------------------------------------------------------------------------
_OPEN, _CLOSE = "([{", ")]}"
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")
_LAYOUT_OPS = ("copy", "copy-start", "copy-done", "transpose", "bitcast",
               "reshape", "slice", "dynamic-slice", "dynamic-update-slice",
               "concatenate", "pad", "broadcast", "reverse", "gather",
               "scatter")
_LAYOUT_WORDS = ("copy", "transpose", "bitcast", "slice", "concatenate",
                 "pad", "data formatting")


def parse_hlo(text: str) -> Tuple[str, str, str]:
    """``(name, opcode, fusion kind)`` of an instruction's HLO text,
    ``%name = <shape> opcode(operands), kind=kLoop, ...``.  A name that is
    not HLO text (another runtime's trace) parses as ``(text, "", "")``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%"), "", ""
    depth, i = 0, 0
    for i, ch in enumerate(rest):          # skip the shape, tuples included
        if ch in _OPEN:
            depth += 1
        elif ch in _CLOSE:
            depth -= 1
        elif ch == " " and depth == 0:
            break
    opcode = rest[i + 1:].partition("(")[0].strip()
    kind = re.search(r"\bkind=k(\w+)", rest)
    return head.lstrip("%"), opcode, kind.group(1) if kind else ""


@lru_cache(maxsize=65536)
def categorise(text: str, stated: Optional[str] = None) -> str:
    """One of :data:`CATEGORIES`.  The trace's own category wins where it
    states one; else the opcode and, for a fusion, its kind (on the TPU an
    output fusion is rooted in a convolution or a dot, an input fusion in a
    reduction); else the substring table copied from
    ``benchmark/profile_step.py:classify``."""
    if stated:
        s = stated.lower()
        if any(c in s for c in _COLLECTIVES):
            return "collective"
        if "convolution" in s or "output fusion" in s or "dot" in s:
            return "matrix"
        if "reduce" in s or "input fusion" in s:
            return "reduce"
        if any(w in s for w in _LAYOUT_WORDS):
            return "layout"
        return "other"
    name, opcode, kind = parse_hlo(text)
    low = name.lower()
    if any(c in opcode or c in low for c in _COLLECTIVES):
        return "collective"
    if opcode in ("convolution", "dot") or kind == "Output":
        return "matrix"
    if opcode in ("reduce", "reduce-window") or kind == "Input":
        return "reduce"
    if opcode in _LAYOUT_OPS or "ConcatBitcast" in text:
        return "layout"
    # the fallback table (profile_step.classify), in its order; its "conv"
    # also matched every "convert" fusion, which is elementwise
    if re.search(r"conv(?!ert)", low):
        return "matrix"
    if "reduce" in low:
        return "reduce"
    if any(w in low for w in _LAYOUT_WORDS):
        return "layout"
    if "dot" in low or "matmul" in low:
        return "matrix"
    return "other"


def short_name(text: str) -> str:
    name, opcode, kind = parse_hlo(text)
    return f"{name} [{opcode}{' k' + kind if kind else ''}]" if opcode \
        else name


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Of two merged, sorted lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(merged: Sequence[Interval], window: Interval) -> List[Interval]:
    out, at = [], window[0]
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event of ONE line with its duration less its children's."""
    out: List[List] = []
    stack: List[int] = []
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and out[stack[-1]][0].end_ns <= ev.start_ns:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(ev.end_ns, out[stack[-1]][0].end_ns) \
                - ev.start_ns
        out.append([ev, ev.end_ns - ev.start_ns])
        stack.append(len(out) - 1)
    return [(ev, max(0.0, t)) for ev, t in out]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def reduce(events: Sequence[Event], steps: Optional[int] = None,
           spans: Sequence[Tuple[str, float, float]] = (),
           top: int = 10) -> Optional[Dict]:
    """All the benchmark reads from a trace, seconds throughout.  ``spans``
    are the benchmark's host spans, ``(name, start_ns, end_ns)`` on the
    trace's clock.  ``None`` when no operation ran on a device (a run on the
    CPU has no device plane: there is nothing to read)."""
    by_plane: Dict[str, Dict[str, List[Event]]] = defaultdict(
        lambda: defaultdict(list))
    for ev in events:
        if is_device_plane(ev.plane):
            by_plane[ev.plane][ev.line].append(ev)
    planes = {p: l for p, l in by_plane.items() if l.get(OPS_LINE)}
    if not planes:
        return None
    window = (min(e.start_ns for l in planes.values() for e in l[OPS_LINE]),
              max(e.end_ns for l in planes.values() for e in l[OPS_LINE]))
    window_ns = window[1] - window[0]

    devices, op_ns = [], defaultdict(float)
    for plane in sorted(planes):
        ops = planes[plane][OPS_LINE]
        busy = union((e.start_ns, e.end_ns) for e in ops)
        category_ns = dict.fromkeys(CATEGORIES, 0.0)
        for ev, t in self_times(ops):
            category_ns[categorise(ev.name, ev.category)] += t
            op_ns[ev.name] += t
        in_flight = [e for e in planes[plane].get(ASYNC_LINE, ())
                     if categorise(e.name) == "collective"]
        collective = union(
            [(e.start_ns, e.end_ns) for e in ops
             if categorise(e.name, e.category) == "collective"]
            + [(e.start_ns, e.end_ns) for e in in_flight])
        compute = union((e.start_ns, e.end_ns) for e in ops
                        if categorise(e.name, e.category) != "collective")
        hidden = measure(intersect(collective, compute))
        devices.append({
            "plane": plane,
            "busy_s": measure(busy) / 1e9,
            "idle_share": 1.0 - measure(busy) / window_ns,
            "category_s": {c: t / 1e9 for c, t in category_ns.items()},
            "collective_s": measure(collective) / 1e9,
            "collective_exposed_s": (measure(collective) - hidden) / 1e9,
            "module_runs": len(planes[plane].get(MODULES_LINE, ())),
            "_busy": busy,
        })

    worst = max(devices, key=lambda d: d["idle_share"])
    gap_ns: Dict[str, float] = defaultdict(float)
    by_label: Dict[str, List[Interval]] = defaultdict(list)
    for label, s, e in spans:
        by_label[label].append((s, e))
    merged_spans = {k: union(v) for k, v in by_label.items()}
    for gap in complement(worst["_busy"], window):
        cover = {k: measure(intersect(v, [gap]))
                 for k, v in merged_spans.items()}
        label = max(cover, key=cover.get) if cover else None
        gap_ns[label if label and cover[label] > 0
               else "no span"] += gap[1] - gap[0]
    for d in devices:
        del d["_busy"]

    n = len(devices)
    ranked = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_ns / 1e9,
        "steps": steps,
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
        "category_s": {c: sum(d["category_s"][c] for d in devices) / n
                       for c in CATEGORIES},
        "device_ops": [[short_name(k), v / 1e9 / n] for k, v in ranked],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(gap_ns.items(), key=lambda kv: -kv[1])[:top]],
    }
