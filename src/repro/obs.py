"""Host spans and counters of the program, kept in memory.

``span(name)``, a context manager or a decorator, times host work on
``time.perf_counter_ns`` and records its name, start and end, its own
id, and the id of the span open around it on the same thread (its
parent).  ``count(name, n)`` adds ``n``
to a counter of the innermost span open on the calling thread; a count
with no span open is dropped.  Closed spans go into a bounded ring (the
oldest fall out) and :func:`records` reads them back.  Nothing is written
out.

Where JAX is imported, each span is also a
``jax.profiler.TraceAnnotation``: in a profiler trace it shows on the host
plane under its name, on the clock of the device's operations.

Recording is on by default.  Spans are per batch on host threads, or
per kernel build while JAX traces, never per token and never in compiled
code; ``set_enabled(False)`` turns recording off.

The program's spans and counters:

* ``planner.plan`` -- ``planner.plan_many``: the planner over one batch;
* ``planner.encode`` -- ``planner.encode_plan_batch``;
* ``planner.tables`` -- ``planner.emit_visit_tables``, with the counters
  ``tables.visits`` (valid kernel visits), ``tables.slots`` (grid slots
  launched: the padded tables' size) and ``tables.pairs`` (doc-causal
  query-key pairs the tables cover), each summed over every table
  returned: both directions, local and hops, every sample and rank;
* ``attn.kernel_build`` -- one per ``pallas_call`` that
  ``kernels.doc_attention``'s fwd, dQ and dK/dV entry points build (at
  trace time, so once per compiled program), with the counters
  ``attn.heads_per_step`` (query heads one grid step carries) and
  ``attn.grid_steps`` (the product of the kernel's grid).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time
from typing import NamedTuple

__all__ = ["RING", "Record", "Recorder", "span", "count", "records",
           "set_enabled"]

#: records the ring keeps
RING = 65536


class Record(NamedTuple):
    """One closed span: name, start and end (ns), its id, the id of the
    span around it on its thread (``None`` at the top), its counters."""
    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: int | None
    counts: dict


class Recorder:
    """Spans and counters of one process, in a ring of ``capacity``."""

    def __init__(self, capacity: int = RING):
        self.enabled = True
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)

    def _open(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.enabled:
            yield
            return
        stack = self._open()
        sid, counts = next(self._ids), {}
        parent = stack[-1][0] if stack else None
        stack.append((sid, counts))
        jax = sys.modules.get("jax")
        ann = jax.profiler.TraceAnnotation(name) if jax else None
        if ann is not None:
            ann.__enter__()
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            if ann is not None:
                ann.__exit__(None, None, None)
            stack.pop()
            with self._lock:
                self._ring.append(Record(name, t0, t1, sid, parent, counts))

    def count(self, name: str, n: int = 1) -> None:
        stack = getattr(self._local, "stack", None)
        if self.enabled and stack:
            counts = stack[-1][1]
            counts[name] = counts.get(name, 0) + int(n)

    def records(self, since_ns: int = 0,
                until_ns: int | None = None) -> list[Record]:
        """Closed spans that ended in ``[since_ns, until_ns]``, oldest
        first."""
        with self._lock:
            recs = list(self._ring)
        return [r for r in recs if r.end_ns >= since_ns
                and (until_ns is None or r.end_ns <= until_ns)]


_RECORDER = Recorder()


def span(name: str):
    """Record the block it wraps as a span of the process's recorder; as a
    decorator, every call of the function it decorates."""
    return _RECORDER.span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost span open on this
    thread."""
    _RECORDER.count(name, n)


def records(since_ns: int = 0, until_ns: int | None = None) -> list[Record]:
    """The process's closed spans that ended in ``[since_ns, until_ns]``."""
    return _RECORDER.records(since_ns, until_ns)


def set_enabled(on: bool) -> None:
    """Turn the process's recording on or off."""
    _RECORDER.enabled = bool(on)
