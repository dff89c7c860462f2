"""The device-program count per task, on a trace written by hand and on
the one recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from chipbench import harness, record, tracing

DATA = Path(__file__).parent / "data"
READ = harness._reader("device_programs_per_task")

# a 1000 ns window on two chips: programs start at 100, 500 and 950 on
# the first (the last runs past the window's end), before it on the second
HAND = {
    "window": [0, 1000],
    "devices": {
        "/device:TPU:0": {
            "ops": [[100, 250], [500, 700], [950, 1100]],
            "programs": [["jit_stack", 100, 250], ["jit__update", 500, 700],
                         ["jit_dynamic_slice", 950, 1100]]},
        "/device:TPU:1": {"ops": [[-50, 50]],
                          "programs": [["jit__trsm", -50, 50]]},
    },
    "spans": [],
}


def _rec(trace, tasks):
    return record.Record(
        cell="potrf.n8192.t512", config={}, traffic={},
        device_kind="TPU v5 lite", n_devices=1, solves=1, tasks=tasks,
        spans={}, compiles_in_window=0, bytes_moved=0, kernels={},
        trace=trace)


def test_counts_programs_that_start_in_the_window():
    tr = tracing.Trace.from_records(HAND)
    assert READ(_rec(tr, tasks=2)) == pytest.approx(1.5)


def test_reads_nothing_without_devices_or_tasks():
    tr = tracing.Trace.from_records(dict(HAND, devices={}))
    assert READ(_rec(tr, tasks=2)) is None
    assert READ(_rec(tracing.Trace.from_records(HAND), tasks=0)) is None
    assert READ(_rec(None, tasks=2)) is None


def test_recorded_trace():
    rec = json.loads((DATA / "potrf_v5e_trace.json").read_text())
    tr = tracing.Trace.from_records(rec["trace"])
    lo, hi = tr.window
    n = sum(lo <= s < hi for d in tr.devices.values()
            for _, s, _ in d.programs)
    assert n > 0
    assert READ(_rec(tr, tasks=n)) == pytest.approx(1.0)
