"""Opt-in ``jax.profiler`` trace-context hook.

When ``RuntimeConfig(profile_waves=True)``, the runtime wraps each step
of its hot path in :func:`trace_span` — a
``jax.profiler.TraceAnnotation`` — so a profile captured with
``jax.profiler.trace()`` (or TensorBoard) puts the host's steps on the
same clock as the device's XLA executions.  The spans, with ``<x>`` the
executor kind (``staged`` or ``sharded``):

* ``bddt/analyze`` — one task's dependence analysis and graph insertion,
  at its spawn;
* ``bddt/<x>/wave<k>`` — the k-th wave the executor ran, around the
  steps below;
* ``bddt/<x>/layer`` — layering a barrier's (or wait's) tasks into waves,
  and grouping one wave's tasks by signature;
* ``bddt/<x>/stack`` — assembling one group's operands (the sharded
  executor also places the tasks on their owner homes here);
* ``bddt/<x>/call`` — the group's jitted body calls (they enqueue the
  work, and compile it on a new shape);
* ``bddt/<x>/store`` — slicing the group's results and committing them;
* ``bddt/<x>/release`` — collecting one wave's executed tasks, and
  releasing their dependents at the end of the barrier.

The steps are leaves: a step opens inside its ``wave<k>`` span or, for
the barrier's own layering and release, at the top.  The flag needs no
tracker; the tracker's ``wall_s`` event fields stay on ``perf_counter``
and only these spans line up with the device.  Disabled (the default)
the executors pass precomputed labels and get back a shared no-op
context manager: no annotation is built and no label formatted.

:func:`profile_session` is the *session* side of the same story: the
annotations only land in a trace file if someone started a profiler
session around the run.  The benchmark driver (``benchmarks.run
--profile-dir``) and the nightly job use it to bracket app runs with
``jax.profiler.start_trace``/``stop_trace`` so ``profile_waves`` spans
end up in uploaded artifacts instead of requiring a hand-started
TensorBoard session.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation, start_trace, stop_trace

__all__ = ["trace_span", "profile_session"]

_NULL = contextlib.nullcontext()


def trace_span(label: str, enabled: bool = True):
    """A context manager naming ``label`` in the jax profiler timeline;
    a no-op when ``enabled`` is False."""
    if not enabled:
        return _NULL
    return TraceAnnotation(label)


@contextlib.contextmanager
def profile_session(logdir: str | None):
    """Bracket a region with a ``jax.profiler`` trace session writing to
    ``logdir``; yields True when a session actually started.

    No-op (yields False) when ``logdir`` is falsy, so callers never need
    to guard.  ``stop_trace`` runs even if the body raises, so partial
    sessions still flush their trace files for upload."""
    if not logdir:
        yield False
        return
    start_trace(str(logdir))
    try:
        yield True
    finally:
        stop_trace()
