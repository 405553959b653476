"""The benchmark's own host spans, around its calls into the program.

Each span is ``(start, end)`` in nanoseconds of ``time.time_ns``: the clock
the profiler's trace is on (an event's ``start_ns`` is the wall time less the
trace's ``profile_start_time``; they agreed to 0.4 us on the v5e host, PR 22).
So ``trace_reduce`` can say what the host was doing in an idle gap with the
profiler's own host tracer OFF: switched on, its events for the input
thread's transfers alone slowed the host enough to idle the chip 66-71% of
some traced windows of ResNet-50, and 1-2% of others.
"""
from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict, List, Tuple


class Spans:
    def __init__(self):
        self.records: Dict[str, List[Tuple[int, int]]] = defaultdict(list)

    @contextmanager
    def __call__(self, name: str):
        t0 = time.time_ns()
        try:
            yield
        finally:
            self.records[name].append((t0, time.time_ns()))

    def seconds(self, name: str) -> List[float]:
        return [(b - a) / 1e9 for a, b in self.records.get(name, ())]

    def since(self, origin_ns: int) -> List[Tuple[str, int, int]]:
        """``(name, start, end)`` relative to ``origin_ns``."""
        return [(name, a - origin_ns, b - origin_ns)
                for name, spans in self.records.items() for a, b in spans]
