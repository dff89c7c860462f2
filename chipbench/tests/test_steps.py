"""The runtime's program step spans in a trace (``chipbench/steps.py``):
their seconds and readers on a hand-written trace where every number is
known, the idle split by the innermost step, the existing reductions left
as they read, and a real capture on the CPU."""
import json

import pytest

from chipbench import harness, record, steps, tracing
from chipbench.tests.conftest import TINY
from chipbench.tests.drive import CELLS, tiny_cell
from chipbench.tests.test_trace import DATA, HAND

STEPS = ("analyze", "layer", "stack", "call", "store", "release")

# HAND with the runtime's steps: two analyses inside the spawn, a layering
# of the barrier before its wave, and inside bddt/staged/wave7 [400, 900)
# the wave's layering, stack, call, store and release, then the barrier's
# release, which runs past the window's end
HAND_STEPS = dict(HAND, spans=sorted(HAND["spans"] + [
    ["bddt/analyze", 50, 120], ["bddt/analyze", 200, 260],
    ["bddt/staged/layer", 300, 350],
    ["bddt/staged/layer", 400, 420], ["bddt/staged/stack", 420, 480],
    ["bddt/staged/call", 480, 520], ["bddt/staged/store", 700, 880],
    ["bddt/staged/release", 880, 900], ["bddt/staged/release", 950, 1050],
], key=lambda s: s[1]))

#: the seconds of each step in HAND_STEPS
HAND_STEP_NS = {"analyze": 130, "layer": 70, "stack": 60, "call": 40,
                "store": 180, "release": 70}


def hand_steps():
    return tracing.Trace.from_records(HAND_STEPS)


def _rec(trace, tasks=10):
    return record.Record(
        cell="potrf.n8192.t512", config={}, traffic={},
        device_kind="TPU v5 lite", n_devices=1, solves=1, tasks=tasks,
        spans={}, compiles_in_window=0, bytes_moved=0, kernels={},
        trace=trace)


def _pattern(step):
    return "bddt/analyze" if step == "analyze" else rf"bddt/\w+/{step}"


@pytest.mark.parametrize("step", STEPS)
def test_span_seconds_and_reader(step):
    tr = hand_steps()
    assert steps.span_seconds(tr, _pattern(step)) == pytest.approx(
        HAND_STEP_NS[step] * 1e-9)
    read = harness._reader(f"{step}_us_per_task")
    assert read(_rec(tr)) == pytest.approx(HAND_STEP_NS[step] * 1e-9
                                           / 10 * 1e6)
    # a program without the span, no trace, no tasks: nothing to read
    assert read(_rec(tracing.Trace.from_records(HAND))) is None
    assert read(_rec(None)) is None
    assert read(_rec(tr, tasks=0)) is None


def test_metrics_name_their_readers_and_the_benchmarks_layers():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    layers = {m["layer"] for m in bench["per_layer"]}
    assert [m["name"] for m in steps.METRICS] == [
        f"{s}_us_per_task" for s in STEPS]
    for m in steps.METRICS:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["layer"] in layers


def test_wave_span_seconds_leave_the_steps_out():
    assert steps.span_seconds(hand_steps(), r"bddt/\w+/wave\d+") == \
        pytest.approx(500e-9)


def test_idle_is_split_by_the_innermost_step():
    got = dict(tracing.idle_by_host_span(hand_steps()))
    # device 0 idle [0,100) [250,500) [700,950); device 1 idle [50,1000);
    # each cut by the innermost span open over it, averaged over devices
    want = {"spawn": (90 + 120) / 2, "bddt/analyze": (60 + 130) / 2,
            "bddt/staged/layer": 70, "barrier": 100,
            "bddt/staged/stack": 60, "bddt/staged/call": (20 + 40) / 2,
            "bddt/staged/wave7": (0 + 180) / 2, "bddt/staged/store": 180,
            "bddt/staged/release": (20 + 70) / 2}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})


def test_existing_reductions_read_as_before():
    for tr in (tracing.Trace.from_records(HAND), hand_steps()):
        assert tracing.busy_seconds(tr) == 2.2500000000000002e-07
        assert tracing.program_seconds(
            tr, tracing.body_matcher("_update")) == 2.0000000000000002e-07
        assert tracing.top_programs(tr) == [
            ["jit__update", 2.0000000000000002e-07],
            ["jit_stack", 1.5000000000000002e-07],
            ["jit_dynamic_update_slice", 5.0000000000000004e-08]]
    rec = json.loads((DATA / "potrf_v5e_trace.json").read_text())
    tr = tracing.Trace.from_records(rec["trace"])
    assert tracing.busy_seconds(tr) == 0.004041892
    assert tracing.program_seconds(
        tr, tracing.body_matcher("_trsm")) == 0.0007930760000000001
    assert tracing.top_programs(tr) == [
        ["jit_dynamic_slice", 0.001659862],
        ["jit_concatenate", 0.0008589890000000001],
        ["jit_broadcast_in_dim", 0.000820594],
        ["jit__trsm", 0.0007930760000000001],
        ["jit__potrf", 9.9417e-05]]
    r = _rec(tr, tasks=0)
    r.kernels = {"_trsm": rec["trsm_cost"]}
    assert record.kernel_roofline(r, "_trsm") == 0.48430890973093726
    # the recorded trace predates the step spans: their readers are silent
    for s in STEPS:
        assert harness._reader(f"{s}_us_per_task")(_rec(tr)) is None


def test_keep_program_spans_restores_the_filter():
    kept = tracing._HOST_SPAN
    with steps.keep_program_spans():
        assert tracing._HOST_SPAN.match("bddt/sharded/stack")
        assert tracing._HOST_SPAN.match("bddt/analyze")
        assert tracing._HOST_SPAN.match("barrier")
        assert tracing._HOST_SPAN.match("bddt/staged/wave12")
        assert not tracing._HOST_SPAN.match("bddt/staged/stacks")
    assert tracing._HOST_SPAN is kept
    assert not kept.match("bddt/staged/stack")


def test_cpu_capture_holds_the_steps_inside_the_harness_spans():
    """A real profiler session on the CPU around a 4x4-tile staged potrf:
    every step span comes back from the ``.xplane.pb``, inside the
    harness's ``spawn`` (the analysis) or ``barrier`` (the waves)."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro import RuntimeConfig, TaskRuntime

    from chipbench.programs import potrf
    from chipbench.refs import potrf as ref

    cfg = {"n": 64}
    inputs = ref.make_inputs(harness.seed_key(7), cfg)
    traces = []
    with steps.keep_program_spans(), tracing.capture(traces):
        with TraceAnnotation(tracing.WINDOW):
            rt = TaskRuntime(RuntimeConfig(executor="staged",
                                           profile_waves=True))
            arrays = {"A": rt.from_array(inputs["A"], (16, 16))}
            with TraceAnnotation("spawn"), rt.scope():
                potrf.spawn(arrays, 4)
            with TraceAnnotation("barrier"):
                rt.barrier()
            jax.block_until_ready(arrays["A"].get_tile((3, 3)))
            rt.shutdown()
    (tr,) = traces
    names = {n for n, _, _ in tr.spans}
    assert {"bddt/analyze"} | {f"bddt/staged/{s}" for s in STEPS[1:]} \
        <= names
    outer = {n: (s, e) for n, s, e in tr.spans if n in ("spawn", "barrier")}
    for n, s, e in tr.spans:
        if n == "bddt/analyze":
            lo, hi = outer["spawn"]
        elif n.startswith("bddt/"):
            lo, hi = outer["barrier"]
        else:
            continue
        assert lo <= s <= e <= hi, (n, s, e)
    # 20 tasks of potrf on a 4x4 grid, one analysis each
    assert sum(n == "bddt/analyze" for n, _, _ in tr.spans) == 20


@pytest.mark.parametrize("w", [w for w in CELLS if w["chips"] == 1][:1],
                         ids=lambda w: w["name"])
def test_traced_run_reads_the_steps(w):
    """A whole traced run at a tiny size, as ``run_steps.py`` makes it:
    the result line adds the six metrics, and the steps fit inside the
    harness spans that the older metrics read."""
    import io
    import time

    import jax

    n, tile = TINY[w["traffic"]]
    cell = tiny_cell(w, n, tile)
    cell.per_layer = cell.per_layer + steps.METRICS
    with steps.keep_program_spans():
        out = harness.run_cell(cell, 2 ** 33 + 5, 0.5, True, jax.devices(),
                               time.perf_counter(),
                               harness.CompileCounter().install(),
                               log=io.StringIO())
    assert out["correct"] is True
    m = out["metrics"]
    for s in STEPS:
        assert m[f"{s}_us_per_task"]["value"] > 0, s
    assert m["analyze_us_per_task"]["value"] <= \
        m["master_us_per_task"]["value"]
    assert sum(m[f"{s}_us_per_task"]["value"] for s in STEPS[1:]) <= \
        m["dispatch_us_per_task"]["value"]
    # a CPU trace has no device plane, so no idle to split: the hand trace
    # above checks the split
    assert out["breakdown"]["idle_gaps"] == []
