"""From a profiler trace to the numbers the per-layer metrics read.

:func:`events_from_xplane` flattens JAX's ``.xplane.pb`` into a list of
plain events, ``{"plane", "line", "name", "meta", "start_ns", "dur_ns"}``
(``meta`` holds a device operation's HLO text).  Everything else here
works on that list, so the reduction can be tested on a small recorded
trace without a chip:

* device operations are the events of a device plane (``/device:...``) on
  one of :data:`OP_LINES` (the compute stream; the asynchronous copies of
  ``Async XLA Ops`` overlap it and are left out), named by the short HLO
  name, with their HLO text in ``meta``;
* host spans are the benchmark's ``TraceAnnotation`` events on a host
  plane, which share the device's clock in the trace;
* busy time is the union of a device's operation intervals inside the
  window, idle share is one minus busy over the window;
* an operation that spans others of its line (a ``while`` loop around
  its body) is a container: it counts towards busy time, and not towards
  an operation's own time or the cover of a collective;
* a collective's exposed time is the part of its interval during which no
  other operation runs on that device;
* an idle gap is named by the host span that overlaps it most.
"""

from __future__ import annotations

import glob
import gzip
import json
import os

__all__ = ["OP_LINES", "HOST_SPANS", "COLLECTIVE_PREFIXES",
           "events_from_xplane", "find_xplane", "save_events",
           "load_events", "kernel_names", "device_ops", "host_spans",
           "union_ns", "clip", "TraceSummary"]

OP_LINES = ("XLA Ops",)
HOST_SPANS = ("next_batch", "h2d", "dispatch", "sync")
COLLECTIVE_PREFIXES = ("collective-permute", "all-gather", "all-reduce",
                       "reduce-scatter", "all-to-all")
_META_CHARS = 4000


def split_op_text(text: str) -> tuple[str, str]:
    """A TPU trace names each operation by its HLO text
    (``%fusion.3 = bf16[...] fusion(...), ...``): return the short name
    (``fusion.3``) and the text, cut to :data:`_META_CHARS`."""
    if text.startswith("%") and " = " in text:
        return text[1:text.index(" = ")], text[:_META_CHARS]
    return text, ""


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def events_from_xplane(path: str, keep_host: tuple = HOST_SPANS) -> list:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        if not is_dev and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not is_dev and ev.name not in keep_host:
                    continue
                name, meta = split_op_text(ev.name) if is_dev \
                    else (ev.name, "")
                out.append({"plane": plane.name, "line": line.name,
                            "name": name, "meta": meta,
                            "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def save_events(events: list, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def kernel_names(bench_dir, kernels: str) -> list:
    """The names that match the kernels of ``bench/kernels/<kernels>.json``
    in a trace."""
    with open(os.path.join(bench_dir, "kernels", f"{kernels}.json")) as f:
        return json.load(f)["names"]


def device_ops(events: list, lines: tuple = OP_LINES) -> dict:
    """{device plane: [event, ...]} of operations on ``lines``, each
    marked ``"container"`` where it holds other operations of its line
    (a ``while`` loop spans the operations of its body)."""
    out: dict[str, list] = {}
    for e in events:
        if e["plane"].startswith("/device:") and e["line"] in lines:
            out.setdefault(e["plane"], []).append(dict(e, container=False))
    for ops in out.values():
        ops.sort(key=lambda e: (e["start_ns"], -e["dur_ns"]))
        stack: list[dict] = []
        for e in ops:
            end = e["start_ns"] + e["dur_ns"]
            while stack and stack[-1]["start_ns"] + stack[-1]["dur_ns"] \
                    <= e["start_ns"]:
                stack.pop()
            if stack and end <= stack[-1]["start_ns"] + stack[-1]["dur_ns"]:
                stack[-1]["container"] = True
            stack.append(e)
    return out


def host_spans(events: list, names: tuple = HOST_SPANS) -> list:
    return sorted((e for e in events if not e["plane"].startswith(
        "/device:") and e["name"] in names), key=lambda e: e["start_ns"])


def clip(iv: tuple, w0: float, w1: float):
    a, b = max(iv[0], w0), min(iv[1], w1)
    return (a, b) if b > a else None


def _merge(ivs) -> list:
    out: list[list[float]] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_ns(ivs) -> float:
    return sum(b - a for a, b in _merge(ivs))


def _subtract(ivs, cover) -> list:
    """Parts of the intervals ``ivs`` not covered by ``cover``."""
    cover = _merge(cover)
    out = []
    for a, b in ivs:
        cur = a
        for c0, c1 in cover:
            if c1 <= cur or c0 >= b:
                continue
            if c0 > cur:
                out.append((cur, c0))
            cur = max(cur, c1)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def _matches(e: dict, names) -> bool:
    text = e["name"] + " " + e.get("meta", "")
    return any(n in text for n in names)


def is_collective(e: dict) -> bool:
    return e["name"].startswith(COLLECTIVE_PREFIXES)


class TraceSummary:
    """Reductions of one traced stretch, over the window ``[w0, w1]``."""

    def __init__(self, events: list, w0: float | None = None,
                 w1: float | None = None, lines: tuple = OP_LINES):
        self.ops = device_ops(events, lines)
        self.spans = host_spans(events)
        if w0 is None or w1 is None:
            if self.spans:
                w0 = self.spans[0]["start_ns"]
                w1 = max(s["start_ns"] + s["dur_ns"] for s in self.spans)
            else:
                allv = [e for v in self.ops.values() for e in v]
                w0 = min(e["start_ns"] for e in allv)
                w1 = max(e["start_ns"] + e["dur_ns"] for e in allv)
        self.w0, self.w1 = float(w0), float(w1)
        self.devices = sorted(self.ops)

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) / 1e9

    def _ivs(self, dev, pred=lambda e: True) -> list:
        out = []
        for e in self.ops.get(dev, ()):
            if pred(e):
                iv = clip((e["start_ns"], e["start_ns"] + e["dur_ns"]),
                          self.w0, self.w1)
                if iv:
                    out.append(iv)
        return out

    def busy_s(self, dev) -> float:
        return union_ns(self._ivs(dev)) / 1e9

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s() / self.window_s

    def op_seconds(self, names) -> float:
        """Summed device seconds, over all devices, of operations (not
        containers) whose name or HLO text holds one of ``names``."""
        return sum(b - a for d in self.devices
                   for a, b in self._ivs(d, lambda e: not e["container"]
                                         and _matches(e, names))) / 1e9

    def exposed_collective_s(self, dev) -> float:
        coll = self._ivs(dev, is_collective)
        other = self._ivs(dev, lambda e: not e["container"]
                          and not is_collective(e))
        return union_ns(_subtract(_merge(coll), other)) / 1e9

    def mean_exposed_collective_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.exposed_collective_s(d)
                   for d in self.devices) / len(self.devices)

    def top_ops(self, n: int = 10) -> list:
        """Operations (not containers) that took most device time, per chip
        (mean over the devices), as ``[name, seconds]``."""
        tot: dict[str, float] = {}
        for d in self.devices:
            for e in self.ops[d]:
                if e["container"]:
                    continue
                iv = clip((e["start_ns"], e["start_ns"] + e["dur_ns"]),
                          self.w0, self.w1)
                if iv:
                    tot[e["name"]] = tot.get(e["name"], 0.0) + iv[1] - iv[0]
        k = max(len(self.devices), 1)
        return [[name, ns / 1e9 / k] for name, ns in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, dev) -> list:
        busy = _merge(self._ivs(dev))
        return _subtract([(self.w0, self.w1)], busy)

    def named_gaps(self, n: int = 10) -> list:
        """The longest idle gaps of the first device, each named by the
        host span overlapping it most, as ``[name, seconds]``."""
        if not self.devices:
            return []
        gaps = sorted(self.idle_gaps(self.devices[0]),
                      key=lambda g: g[0] - g[1])[:n]
        out = []
        for a, b in gaps:
            best, best_ov = "none", 0.0
            for s in self.spans:
                ov = min(b, s["start_ns"] + s["dur_ns"]) - max(
                    a, s["start_ns"])
                if ov > best_ov:
                    best, best_ov = s["name"], ov
            out.append([best, (b - a) / 1e9])
        return out
