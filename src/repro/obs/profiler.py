"""Opt-in ``jax.profiler`` trace-context hook.

When ``RuntimeConfig(profile_waves=True)``, the staged/sharded executors
wrap every wave dispatch in :func:`trace_span` — a
``jax.profiler.TraceAnnotation`` — so a device profile captured with
``jax.profiler.trace()`` (or TensorBoard) shows which XLA executions
belong to which wave.  Disabled (the default) the span is a shared
no-op context manager and costs nothing.

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
