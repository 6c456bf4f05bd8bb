"""plan_ms: mean host milliseconds of the program's ``planner.plan`` span
(``planner.plan_many``: the planner over one batch), over the spans that
ended inside the window, read from ``repro.obs`` in this process.  Layer:
planner.  Program span."""

from harness.named import span_ms


def read(rec):
    return span_ms(rec, "planner.plan")
