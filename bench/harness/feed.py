"""The batch feed: generated sequences through the program's planner.

For each stream step the feed draws the packed sequences from the
benchmark's traffic generator and hands their document lengths to the
program's planner, encoder and visit-table calls, with the program's own
``PipelineConfig``: the one ``launch/train.py::pipeline_config`` builds
from the run's ``ModelConfig`` and ``RunConfig`` (the planner after
``effective_strategy``, the alignment, whether tables are emitted, their
overlap and grid).  The calls are composed as the program's
``data/pipeline.make_batch`` composes them (``plan_many``, then
``encode_plan_batch``, then ``emit_visit_tables``); tokens and labels are
then permuted from packed order into plan order.  The program receives
nothing but these generated inputs.

The plan cache and the table cache stay off, with one planner worker: the
stream repeats its pass only so that runs are alike, and fresh data in a
training job would not hit them.

:class:`Feed` runs that work on a background thread, a bounded queue of
``depth`` batches ahead of the step loop (the depth of the program's
``Prefetcher``), and records a ``plan_encode`` span per batch.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import types

import numpy as np

from .spans import Spans
from .traffic import SeqSample, Traffic, draw_step

__all__ = ["plan_config", "HostBatch", "build_batch", "signature", "Feed"]


def plan_config(cfg, run, cp: int, traffic: Traffic):
    """The program's ``PipelineConfig`` for a run of ``traffic`` with the
    program's ``ModelConfig`` ``cfg`` and ``RunConfig`` ``run`` at CP
    ``cp``, with its plan cache off and one planner worker."""
    from repro.launch.train import pipeline_config

    args = types.SimpleNamespace(dataset=traffic.name,
                                 seq_len=traffic.context_len,
                                 batch=traffic.batch)
    pipe = pipeline_config(args, cfg, run, cp)
    if pipe.target_imbalance != run.target_imbalance:
        raise NotImplementedError(
            f"run.target_imbalance {run.target_imbalance!r}: the program's "
            f"pipeline_config plans to {pipe.target_imbalance!r}")
    return dataclasses.replace(pipe, cache_plans=False, planner_workers=1)


@dataclasses.dataclass
class HostBatch:
    step: int
    samples: list[SeqSample]   # packed order (what the reference reads)
    arrays: dict               # plan order, the step program's inputs
    tokens: int                # tokens of the step (batch x context)


def build_batch(pipe, samples: list[SeqSample], step: int) -> HostBatch:
    """The step's arrays, in plan order, for the packed ``samples`` under
    the program's ``PipelineConfig`` ``pipe`` (see :func:`plan_config`).

    A copy: this mirrors ``_planner_state``, ``_plan``, ``_emit_tables``
    and ``make_batch`` of the program's ``data/pipeline.py``, which take
    no given document lengths, and must change with them until the
    program has one entry for both.  ``tests/bench/test_bench_feed.py``
    holds the two to the same arrays."""
    from repro.core.cp_attention import resolve_overlap
    from repro.planner import (emit_visit_tables, encode_plan_batch,
                               get_planner, plan_many)

    planner = get_planner(pipe.strategy)
    kwargs = {}
    if planner.info.supports_target_ratio:
        kwargs["target_ratio"] = pipe.target_imbalance
    plans = plan_many(lambda lens: planner(lens, pipe.cp_size, **kwargs),
                      [s.doc_lens for s in samples],
                      workers=pipe.planner_workers)
    stack, _ = encode_plan_batch(plans, buf_len=pipe.buf_len,
                                 align=pipe.align, workers=1)
    arrays = {k: v for k, v in stack.items() if k != "perm"}
    if pipe.emit_tables:
        exec_style = planner.info.exec_style
        gath = exec_style in ("flashcp", "contiguous")
        arrays.update(emit_visit_tables(
            stack["doc"], stack["pos"],
            stack["gath_doc"] if gath else None,
            stack["gath_pos"] if gath else None,
            num_workers=pipe.cp_size, strategy=exec_style,
            overlap=resolve_overlap(exec_style, "pallas", pipe.table_overlap),
            grid=pipe.table_grid, block_q=pipe.table_block_q,
            block_k=pipe.table_block_k, cache=False))
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

    def __init__(self, traffic: Traffic, pipe, seed: int, spans: Spans, *,
                 depth: int = 2, transform=None):
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._args = (traffic, pipe, seed)
        self._spans = spans
        self._transform = transform
        self.error: BaseException | None = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-feed")
        self._thread.start()

    def _run(self):
        traffic, pipe, seed = self._args
        step = 0
        try:
            while not self._stop.is_set():
                samples = draw_step(traffic, seed, step, pipe.vocab_size)
                with self._spans.span("plan_encode"):
                    hb = build_batch(pipe, samples, step)
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
