"""Executor waves: host microseconds per task spent slicing each
group's results and committing them to the tiles (the runtime's
``bddt/<executor>/store`` spans, over the tasks spawned in the window)."""
from chipbench.steps import span_us_per_task


def read(rec):
    return span_us_per_task(rec, r"bddt/\w+/store")
