"""Host spans of the benchmark's own calls into the program.

Each span is written into the profiler's trace (``TraceAnnotation``), so
a traced run can say what the host was doing while the device idled, and
kept in memory, so per-layer readers can time host phases without a
trace."""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax


class Spans:
    """Named host intervals, in ``time.perf_counter`` seconds."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.mark = 0.0   # records before this time belong to set-up

    @contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.records.append((name, t0, time.perf_counter()))

    def in_window(self, name: str) -> list[float]:
        """Durations (s) of the spans called ``name`` that began in the
        measured window."""
        return [t1 - t0 for n, t0, t1 in self.records
                if n == name and t0 >= self.mark]

    def within(self, t0: float, t1: float) -> list[list]:
        """``[name, seconds]`` of each span that began in ``[t0, t1)``."""
        return [[n, round(b - a, 4)] for n, a, b in self.records
                if t0 <= a < t1]
