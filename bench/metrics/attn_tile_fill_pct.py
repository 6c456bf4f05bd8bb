"""attn_tile_fill_pct: 100 x doc-causal query-key pairs / (valid kernel
visits x block_q x block_k), over the visit tables the program emitted in
the window (the counters ``tables.pairs`` and ``tables.visits`` of its
``planner.tables`` spans, read from ``repro.obs`` in this process): the
share of the visited tiles' cells that attention needs, the rest being
masked out inside the kernel.  The blocks are the feed's (``PlanSpec``).
Layer: attention kernels.  Program counter."""

from harness.feed import PlanSpec
from harness.named import program_spans


def read(rec):
    spans = program_spans(rec, "planner.tables")
    visits = sum(r.counts.get("tables.visits", 0) for r in spans)
    if not visits:
        return None
    pairs = sum(r.counts.get("tables.pairs", 0) for r in spans)
    return 100.0 * pairs / (visits * PlanSpec.block_q * PlanSpec.block_k)
