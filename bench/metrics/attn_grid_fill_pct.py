"""attn_grid_fill_pct: 100 x valid kernel visits / grid slots launched, over
the visit tables the program emitted in the window (the counters
``tables.visits`` and ``tables.slots`` of its ``planner.tables`` spans,
read from ``repro.obs`` in this process).  The rest of the slots are the
padding of the power-of-two buckets and the sentinels of empty rows.
Layer: attention kernels.  Program counter."""

from harness.named import program_spans


def read(rec):
    spans = program_spans(rec, "planner.tables")
    slots = sum(r.counts.get("tables.slots", 0) for r in spans)
    if not slots:
        return None
    return 100.0 * sum(r.counts.get("tables.visits", 0)
                       for r in spans) / slots
