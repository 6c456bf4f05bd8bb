"""tables_ms: mean host milliseconds per batch of the program's
``planner.encode`` span (``encode_plan_batch``) plus its
``planner.tables`` span (``emit_visit_tables``), over the spans that ended
inside the window, read from ``repro.obs`` in this process.  Layer:
planner.  Program span."""

from harness.named import span_ms


def read(rec):
    enc, tab = span_ms(rec, "planner.encode"), span_ms(rec, "planner.tables")
    if enc is None or tab is None:
        return None
    return enc + tab
