"""What one run hands the per-layer metric readers.

A reader is a file ``chipbench/metrics/<metric name>.py`` with a function
``read(rec: Record) -> float | None``.  It returns None when it finds
nothing to read in this run, and the harness then leaves the metric out.
"""
from __future__ import annotations

import dataclasses

from chipbench import peaks, tracing


@dataclasses.dataclass
class Record:
    cell: str
    config: dict
    traffic: dict
    device_kind: str
    n_devices: int
    solves: int               # solves completed in the window
    tasks: int                # tasks spawned in the window
    spans: dict               # harness span name -> seconds in the window
    compiles_in_window: int   # programs compiled or loaded in the window
    bytes_moved: int          # cross-device tile bytes of the window's solves
    kernels: dict             # body -> {"tasks", "flops", "bytes"} per solve
    trace: tracing.Trace | None = None


def kernel_roofline(rec: Record, body: str) -> float | None:
    """Percent of the roofline that the ``body`` programs reach: the least
    time one chip needs for their operations and bytes in the window's
    solves, over their device time in the trace (summed over chips)."""
    cost = rec.kernels.get(body)
    if rec.trace is None or cost is None or not rec.solves:
        return None
    seconds = tracing.program_seconds(rec.trace, tracing.body_matcher(body))
    if seconds <= 0:
        return None
    least = peaks.least_seconds(cost["flops"] * rec.solves,
                                cost["bytes"] * rec.solves, rec.device_kind)
    return 100.0 * least / seconds
