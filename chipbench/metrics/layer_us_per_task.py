"""Executor waves: host microseconds per task spent layering the task
graph into waves and grouping each wave by signature (the runtime's
``bddt/<executor>/layer`` spans, over the tasks spawned in the window)."""
from chipbench.steps import span_us_per_task


def read(rec):
    return span_us_per_task(rec, r"bddt/\w+/layer")
