"""input_wait_ms: mean host milliseconds per window step that the step loop
waits on the feed's queue (the benchmark's ``next_batch`` span).  Layer:
data pipeline.  Host clock."""


def read(rec):
    a, b = rec.window_ns
    d = rec.spans.durations_s("next_batch", a, b)
    return 1e3 * sum(d) / len(d) if d else None
