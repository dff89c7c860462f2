"""Executor waves: host microseconds per task spent assembling each
group's operands: stacking tiles, and on a mesh placing tasks on their
owners (the runtime's ``bddt/<executor>/stack`` spans, over the tasks
spawned in the window)."""
from chipbench.steps import span_us_per_task


def read(rec):
    return span_us_per_task(rec, r"bddt/\w+/stack")
