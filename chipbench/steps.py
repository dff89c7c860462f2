"""The runtime's own program spans in a run's trace, and the per-layer
numbers read from them.

With ``profile_waves`` on, the runtime opens a ``jax.profiler``
annotation around each step of its hot path, on the same clock as the
device trace:

* ``bddt/analyze`` — one task's dependence analysis and graph insertion;
* ``bddt/<executor>/layer`` — layering a barrier's tasks into waves, and
  grouping each wave by signature;
* ``bddt/<executor>/stack`` — assembling one group's operands (on a mesh
  also placing the tasks on their owners);
* ``bddt/<executor>/call`` — the group's jitted body calls;
* ``bddt/<executor>/store`` — slicing and committing the group's results;
* ``bddt/<executor>/release`` — collecting a wave's tasks, and releasing
  their dependents at the end of the barrier.

The harness's capture keeps only the host spans of
``tracing._HOST_SPAN``, which does not name these yet, so the benchmark's
result line does not carry the six ``*_us_per_task`` metrics their
readers (``chipbench/metrics/``) compute. :func:`keep_program_spans`
widens that filter for one run, and ``chipbench/run_steps.py`` runs a
cell traced with it.
"""
from __future__ import annotations

import contextlib
import re

from chipbench import tracing

#: every program step span of the runtime
PROGRAM_SPAN = r"bddt/analyze|bddt/\w+/(layer|stack|call|store|release)"
#: the harness's host spans and the program's step spans
HOST_SPAN = re.compile(
    rf"{tracing._HOST_SPAN.pattern}|^({PROGRAM_SPAN})$")

#: the per-layer metric read from each step span
METRICS = [
    {"name": f"{step}_us_per_task", "unit": "us", "better": "lower",
     "source": "program_span", "layer": layer, "moves": "solve_s"}
    for step, layer in [
        ("analyze", "master: front end and dependence analysis")] + [
        (step, "executor waves: layering, stacking, dispatch, stores")
        for step in ("layer", "stack", "call", "store", "release")]]


@contextlib.contextmanager
def keep_program_spans():
    """Keep the program's step spans in every trace captured inside."""
    kept = tracing._HOST_SPAN
    tracing._HOST_SPAN = HOST_SPAN
    try:
        yield
    finally:
        tracing._HOST_SPAN = kept


def span_seconds(trace: tracing.Trace, pattern: str) -> float | None:
    """Host seconds of the spans whose whole name matches the regular
    expression ``pattern``, clipped to the window; None when no span
    matches (the program under test does not open it)."""
    lo, hi = trace.window
    pat = re.compile(pattern)
    found = [(s, e) for name, s, e in trace.spans if pat.fullmatch(name)]
    if not found:
        return None
    return sum(e - s for s, e in tracing._clip(found, lo, hi)) * 1e-9


def span_us_per_task(rec, pattern: str) -> float | None:
    """Host microseconds per task spawned in the window inside the spans
    matching ``pattern``; None without a trace, tasks, or such spans."""
    if rec.trace is None or not rec.tasks:
        return None
    seconds = span_seconds(rec.trace, pattern)
    if seconds is None:
        return None
    return seconds / rec.tasks * 1e6
