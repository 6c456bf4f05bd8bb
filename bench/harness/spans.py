"""Host spans of the benchmark's own, around its calls into the program.

Each span records its name, start and end on the host clock
(``time.perf_counter_ns``).  While a trace is being taken the span is also
written into the profiler's trace as a ``TraceAnnotation``, on the same
clock as the device's operations, so idle gaps on the device can be named
by the host span that was open during them.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax

__all__ = ["Spans"]


class Spans:
    def __init__(self):
        self._lock = threading.Lock()
        self.records: list[tuple[str, int, int]] = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = jax.profiler.TraceAnnotation(name) if self.annotate else None
        t0 = time.perf_counter_ns()
        if ann is not None:
            ann.__enter__()
        try:
            yield
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
            t1 = time.perf_counter_ns()
            with self._lock:
                self.records.append((name, t0, t1))

    def durations_s(self, name: str, since_ns: int = 0,
                    until_ns: int | None = None) -> list[float]:
        with self._lock:
            recs = list(self.records)
        return [(b - a) / 1e9 for n, a, b in recs
                if n == name and a >= since_ns
                and (until_ns is None or b <= until_ns)]
