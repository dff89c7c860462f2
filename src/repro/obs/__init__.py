"""``repro.obs`` — wave-level observability for the task runtime.

The runtime is instrumented at one emit point (``TaskRuntime`` owns the
tracker, every executor reports through it) with a single structured
event schema (:mod:`~repro.obs.events`): wave open/close with dispatch
wall time and measured tile movement, per-dispatch timings and modes,
live per-channel queue depth, owner overrides, host-worker tile-cache
counters, and the DES's predicted-vs-configured cost.  Sinks are
pluggable (:mod:`~repro.obs.tracker`): in-memory for tests, JSONL trace
files for CI artifacts, a console summary for quickstarts.  Traces
export to Chrome/Perfetto JSON (:mod:`~repro.obs.chrome`) and an opt-in
``jax.profiler`` annotation ties waves to device profiles
(:mod:`~repro.obs.profiler`).

Enable per runtime::

    with TaskRuntime(executor="staged", tracker="console") as rt:
        ...

or hand in a sink to keep::

    trk = InMemoryTracker()
    with TaskRuntime(executor="sharded", tracker=trk) as rt:
        ...
    waves = trk.events_of("wave_close")

See docs/OBSERVABILITY.md for the event schema and trace workflow.
"""
from .chrome import chrome_trace, export_chrome_trace, load_jsonl
from .events import EVENT_FIELDS, EVENT_SCHEMA, Event, validate_event
from .profiler import profile_session, trace_span
from .summary import mode_latency, slowest_waves, summary_table
from .tracker import (NULL_TRACKER, ConsoleTracker, InMemoryTracker,
                      JsonlTracker, NullTracker, Tracker, TrackerBase,
                      make_tracker, validate_spec)

__all__ = [
    "EVENT_FIELDS", "EVENT_SCHEMA", "Event", "validate_event",
    "Tracker", "TrackerBase", "NullTracker", "NULL_TRACKER",
    "InMemoryTracker", "JsonlTracker", "ConsoleTracker",
    "make_tracker", "validate_spec",
    "chrome_trace", "export_chrome_trace", "load_jsonl",
    "slowest_waves", "mode_latency", "summary_table",
    "trace_span", "profile_session",
]
