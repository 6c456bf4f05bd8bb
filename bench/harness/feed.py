"""The batch feed: generated sequences through the program's planner.

For each stream step the feed draws the packed sequences from the
benchmark's traffic generator and hands their document lengths to the
program's public planner and encoder calls, composed as the program's
``data/pipeline.make_batch`` composes them: the planner resolved by
``get_planner`` through ``plan_many``, then ``encode_plan_batch``, then
``emit_visit_tables``; tokens and labels are then permuted from packed
order into plan order.  The program receives nothing but these generated
inputs.

:class:`Feed` runs that work on a background thread, a bounded queue of
``depth`` batches ahead of the step loop (the depth of the program's
``Prefetcher``), and records a ``plan_encode`` span per batch.
"""

from __future__ import annotations

import dataclasses
import queue
import threading

import numpy as np

from .spans import Spans
from .traffic import SeqSample, Traffic, draw_step

__all__ = ["PlanSpec", "HostBatch", "build_batch", "signature", "Feed"]


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """What the planner side needs from the run configuration."""
    strategy: str
    cp: int
    overlap: str
    grid: str
    target_imbalance: float
    align: int = 128
    block_q: int = 128
    block_k: int = 128


@dataclasses.dataclass
class HostBatch:
    step: int
    samples: list[SeqSample]   # packed order (what the reference reads)
    arrays: dict               # plan order, the step program's inputs
    tokens: int                # tokens of the step (batch x context)


def build_batch(spec: PlanSpec, samples: list[SeqSample],
                step: int) -> HostBatch:
    from repro.core.cp_attention import resolve_overlap
    from repro.planner import (emit_visit_tables, encode_plan_batch,
                               get_planner, plan_many)

    planner = get_planner(spec.strategy)
    kwargs = {}
    if planner.info.supports_target_ratio:
        kwargs["target_ratio"] = spec.target_imbalance
    plans = plan_many(lambda lens: planner(lens, spec.cp, **kwargs),
                      [s.doc_lens for s in samples], workers=1)
    stack, _ = encode_plan_batch(plans, align=spec.align, workers=1)
    exec_style = planner.info.exec_style
    arrays = {k: v for k, v in stack.items() if k != "perm"}
    arrays.update(emit_visit_tables(
        stack["doc"], stack["pos"],
        stack["gath_doc"] if exec_style in ("flashcp", "contiguous")
        else None,
        stack["gath_pos"] if exec_style in ("flashcp", "contiguous")
        else None,
        num_workers=spec.cp, strategy=exec_style,
        overlap=resolve_overlap(exec_style, "pallas", spec.overlap),
        grid=spec.grid, block_q=spec.block_q, block_k=spec.block_k,
        cache=False))
    perm = stack["perm"]
    tokens = np.full(perm.shape, -1, np.int32)
    labels = np.full(perm.shape, -1, np.int32)
    for b, s in enumerate(samples):
        valid = perm[b] >= 0
        tokens[b, valid] = s.tokens[perm[b, valid]]
        labels[b, valid] = s.labels[perm[b, valid]]
    arrays["tokens"] = tokens
    arrays["labels"] = labels
    return HostBatch(step=step, samples=samples, arrays=arrays,
                     tokens=sum(len(s.tokens) for s in samples))


def signature(arrays: dict) -> tuple:
    """The step program a batch needs: its arrays' names, shapes, dtypes."""
    return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                        for k, v in arrays.items()))


class Feed:
    """Background thread producing :class:`HostBatch` es from step 0 on."""

    def __init__(self, traffic: Traffic, spec: PlanSpec, seed: int,
                 vocab: int, spans: Spans, *, depth: int = 2,
                 transform=None):
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._args = (traffic, spec, seed, vocab)
        self._spans = spans
        self._transform = transform
        self.error: BaseException | None = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-feed")
        self._thread.start()

    def _run(self):
        traffic, spec, seed, vocab = self._args
        step = 0
        try:
            while not self._stop.is_set():
                samples = draw_step(traffic, seed, step, vocab)
                with self._spans.span("plan_encode"):
                    hb = build_batch(spec, samples, step)
                if self._transform is not None:
                    hb = self._transform(hb)
                while not self._stop.is_set():
                    try:
                        self._q.put(hb, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                step += 1
        except BaseException as e:  # surfaced to the step loop by get()
            self.error = e

    def get(self) -> HostBatch:
        while True:
            try:
                return self._q.get(timeout=0.2)
            except queue.Empty:
                if self.error is not None:
                    raise RuntimeError("batch feed failed") from self.error

    def close(self, timeout: float = 60.0) -> None:
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("batch feed thread did not stop")
