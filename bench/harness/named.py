"""What the program names itself: its kernels in the trace, its spans and
counters in memory.

* A Pallas kernel's ``pallas_call(name=...)`` becomes the name of its
  custom-call instruction (``%doc_attn_fwd.3 = ... custom-call(...)``).
  A device operation is therefore matched by its short name with the
  ``.N`` suffix taken off, never by its HLO text: the operations that
  consume a kernel's outputs quote ``%doc_attn_fwd.3`` as an operand.
* ``repro.obs`` keeps the program's host spans, with their counters, in
  memory in this process.  On a program without that module nothing is
  found, and the readers report nothing.
"""

from __future__ import annotations

import re

from .trace import TraceSummary, clip

__all__ = ["base_name", "kernel_intervals", "kernel_ms", "program_spans",
           "span_ms"]

_SUFFIX = re.compile(r"\.\d+$")


def base_name(name: str) -> str:
    """``doc_attn_fwd`` of ``doc_attn_fwd.3`` (or of its HLO text)."""
    if name.startswith("ROOT "):
        name = name[len("ROOT "):]
    if name.startswith("%") and " = " in name:
        name = name[1:name.index(" = ")]
    return _SUFFIX.sub("", name)


def kernel_intervals(summary: TraceSummary, kernel: str) -> list:
    """``(start_ns, end_ns)`` of each launch of ``kernel`` (a named
    operation, not a container) on any device, cut to the traced
    window."""
    out = []
    for dev in summary.devices:
        for e in summary.ops[dev]:
            if e["container"] or base_name(e["name"]) != kernel:
                continue
            iv = clip((e["start_ns"], e["start_ns"] + e["dur_ns"]),
                      summary.w0, summary.w1)
            if iv:
                out.append(iv)
    return out


def kernel_ms(rec, kernel: str) -> float | None:
    """Device ms per traced step in ``kernel``, summed over the devices
    and divided by the cell's chips; ``None`` where it never ran."""
    if rec.trace is None or not rec.traced:
        return None
    ns = sum(b - a for a, b in kernel_intervals(rec.trace, kernel))
    if ns <= 0:
        return None
    return ns / 1e6 / rec.chips / len(rec.traced)


def program_spans(rec, name: str) -> list:
    """The program's ``repro.obs`` spans called ``name`` that ended inside
    the measured window."""
    try:
        from repro import obs
    except ImportError:
        return []
    a, b = rec.window_ns
    return [r for r in obs.records(a, b) if r.name == name]


def span_ms(rec, name: str) -> float | None:
    """Mean ms of the program's spans ``name`` ended inside the window."""
    spans = program_spans(rec, name)
    if not spans:
        return None
    return sum(r.end_ns - r.start_ns for r in spans) / 1e6 / len(spans)
