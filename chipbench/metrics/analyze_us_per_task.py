"""Master: host microseconds per task in dependence analysis and task
graph insertion (the runtime's ``bddt/analyze`` span, one per task spawned,
over the tasks spawned in the window)."""
from chipbench.steps import span_us_per_task


def read(rec):
    return span_us_per_task(rec, r"bddt/analyze")
