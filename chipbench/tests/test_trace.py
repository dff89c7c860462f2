"""The reduction from a device trace to the per-layer numbers, on small
traces: one written by hand, where every number is known, and one
recorded on a TPU v5e (the first 0.3 s of a ``potrf.n8192.t512``
window)."""
import json
from pathlib import Path

import pytest

from chipbench import peaks, record, tracing

DATA = Path(__file__).parent / "data"

# a 1000 ns window; two devices; host spans spawn [0, 300), barrier
# [300, 1000) with a wave inside it [400, 900)
HAND = {
    "window": [0, 1000],
    "devices": {
        "/device:TPU:0": {
            "ops": [[100, 200], [150, 250], [500, 700], [950, 1100]],
            "programs": [["jit__update", 500, 700], ["jit_stack", 100, 250],
                         ["jit_dynamic_update_slice", 950, 1100]]},
        "/device:TPU:1": {"ops": [[-50, 50]], "programs": []},
    },
    "spans": [["spawn", 0, 300], ["barrier", 300, 1000],
              ["bddt/staged/wave7", 400, 900]],
}


def hand():
    return tracing.Trace.from_records(HAND)


def test_busy_is_the_union_of_ops_clipped_to_the_window():
    # device 0: [100, 250) + [500, 700) + [950, 1000) = 400; device 1: 50
    assert tracing.busy_seconds(hand()) == pytest.approx(225e-9)


def test_program_seconds_matches_the_body_only():
    tr = hand()
    assert tracing.program_seconds(tr, tracing.body_matcher("_update")) == \
        pytest.approx(200e-9)
    m = tracing.body_matcher("_update")
    assert m("jit__update")
    assert not m("jit_dynamic_update_slice") and not m("jit__update_x")


def test_idle_is_split_by_the_innermost_host_span():
    got = dict(tracing.idle_by_host_span(hand()))
    # device 0 idle: [0,100) spawn, [250,300) spawn, [300,400) barrier,
    # [400,500) wave, [700,900) wave, [900,950) barrier
    # device 1 idle: [50,300) spawn, [300,400) + [900,1000) barrier,
    # [400,900) wave; averaged over the two devices
    assert got["spawn"] == pytest.approx((150 + 250) / 2 * 1e-9)
    assert got["barrier"] == pytest.approx((150 + 200) / 2 * 1e-9)
    assert got["bddt/staged/wave7"] == pytest.approx((300 + 500) / 2 * 1e-9)
    assert sum(got.values()) == pytest.approx(
        2 * 1000e-9 / 2 - tracing.busy_seconds(hand()))


def test_top_programs_sum_over_devices_within_the_window():
    got = dict(tracing.top_programs(hand()))
    assert got == pytest.approx({"jit__update": 200e-9, "jit_stack": 150e-9,
                                 "jit_dynamic_update_slice": 50e-9})


def test_roofline_share_of_a_recorded_trace():
    rec = json.loads((DATA / "potrf_v5e_trace.json").read_text())
    tr = tracing.Trace.from_records(rec["trace"])
    assert tr.devices and tr.spans
    busy = tracing.busy_seconds(tr)
    assert 0 < busy <= tr.window_s
    trsm = tracing.program_seconds(tr, tracing.body_matcher("_trsm"))
    assert trsm > 0
    cost = rec["trsm_cost"]
    r = record.Record(
        cell="potrf.n8192.t512", config={}, traffic={},
        device_kind="TPU v5 lite", n_devices=1, solves=1, tasks=0, spans={},
        compiles_in_window=0, bytes_moved=0,
        kernels={"_trsm": cost, "_update": cost},
        trace=tr)
    share = record.kernel_roofline(r, "_trsm")
    assert share == pytest.approx(100 * peaks.least_seconds(
        cost["flops"], cost["bytes"], "TPU v5 lite") / trsm)
    assert 0 < share <= 100
    # the slice holds no trailing update: its reader finds nothing
    assert record.kernel_roofline(r, "_update") is None
    idle = dict(tracing.idle_by_host_span(tr))
    assert sum(idle.values()) == pytest.approx(tr.window_s - busy)
