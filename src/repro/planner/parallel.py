"""Parallel multi-sequence planning.

``make_batch`` plans ``batch_per_host`` packed sequences per step; with the
vectorized planner each plan is numpy-dominated and releases the GIL for
most of its runtime, so a small thread pool overlaps them nearly linearly.
The pool is deliberately thread- (not process-) based: plans are built
from shared ``PlanCache`` state and the arrays never need pickling.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Sequence

from repro import obs

__all__ = ["PlannerPool", "get_pool", "plan_many"]


class PlannerPool:
    """Thin ThreadPoolExecutor wrapper that preserves input order."""

    def __init__(self, max_workers: int):
        self.max_workers = max(int(max_workers), 1)
        self._ex = ThreadPoolExecutor(
            max_workers=self.max_workers,
            thread_name_prefix="repro-planner") if self.max_workers > 1 \
            else None

    def map(self, fn: Callable, items: Sequence) -> list:
        if self._ex is None or len(items) <= 1:
            return [fn(x) for x in items]
        return list(self._ex.map(fn, items))

    def close(self) -> None:
        if self._ex is not None:
            self._ex.shutdown(wait=False)
            self._ex = None

    def __del__(self):  # pragma: no cover - best-effort cleanup
        try:
            self.close()
        except Exception:
            pass


_POOLS: dict[int, PlannerPool] = {}


def get_pool(max_workers: int) -> PlannerPool:
    """Shared per-process pool (one per worker count)."""
    max_workers = max(int(max_workers), 1)
    pool = _POOLS.get(max_workers)
    if pool is None or pool._ex is None and pool.max_workers > 1:
        pool = _POOLS[max_workers] = PlannerPool(max_workers)
    return pool


def default_workers(batch: int) -> int:
    """Pool width for one host batch: no wider than the batch, capped by
    the host's CPU count (minus one for the training loop)."""
    cpus = os.cpu_count() or 1
    return max(1, min(int(batch), cpus - 1))


@obs.span("planner.plan")
def plan_many(plan_fn: Callable, lens_list: Sequence, *,
              workers: int = 0) -> list:
    """Plan every length mix in ``lens_list``; ``workers=0`` auto-sizes."""
    if workers <= 0:
        workers = default_workers(len(lens_list))
    return get_pool(workers).map(plan_fn, lens_list)
