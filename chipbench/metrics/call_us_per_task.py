"""Executor waves: host microseconds per task spent in the jitted body
calls, which enqueue the work, or compile it for a new shape (the
runtime's ``bddt/<executor>/call`` spans, over the tasks spawned in the
window)."""
from chipbench.steps import span_us_per_task


def read(rec):
    return span_us_per_task(rec, r"bddt/\w+/call")
