"""Executor waves: host microseconds per task spent collecting executed
tasks and releasing their dependents (the runtime's
``bddt/<executor>/release`` spans, over the tasks spawned in the window)."""
from chipbench.steps import span_us_per_task


def read(rec):
    return span_us_per_task(rec, r"bddt/\w+/release")
