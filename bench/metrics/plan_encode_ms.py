"""plan_encode_ms: mean host milliseconds per batch the feed thread spends
in the planner, the encoder and the visit-table emission (the benchmark's
``plan_encode`` span), over batches finished inside the window.  Layer:
planner.  Host clock."""


def read(rec):
    a, b = rec.window_ns
    d = rec.spans.durations_s("plan_encode", a, b)
    return 1e3 * sum(d) / len(d) if d else None
