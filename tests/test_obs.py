"""repro.obs (ISSUE 6): the wave-level observability subsystem.

Covers the tentpole's acceptance surface: (a) the event schema is
stable and every emitted event validates against it; (b) event counts
are deterministic per executor on a fixed gemm graph, through both the
in-memory and JSONL sinks; (c) the Chrome-trace exporter produces valid
trace JSON with monotonic timestamps; (d) feeding the tracker's live
queue depth into ``rebalance_owners`` is equivalent to the wave-local
path on unskewed waves, and on a forced-host 2-device mesh the
queue-depth-fed override preserves ``bytes_staged == 0`` and
bit-identical results; (e) a disabled tracker means *zero* emitted
events and no emit calls on the hot path (guarded by a spy, not a wall
clock).  Plus the satellites: host-worker pinned tile caches with
hit/miss counters, the ``RuntimeStats`` to/from-JSON round-trip, the
bench timings block validation, and the console/summary rendering.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import RuntimeConfig, RuntimeStats, TaskRuntime, task
from repro.core.api import STATS_SCHEMA
from repro.core.placement import rebalance_owners
from repro.obs import (EVENT_FIELDS, EVENT_SCHEMA, ConsoleTracker, Event,
                       InMemoryTracker, JsonlTracker, NULL_TRACKER,
                       NullTracker, Tracker, chrome_trace,
                       export_chrome_trace, load_jsonl, make_tracker,
                       mode_latency, slowest_waves, summary_table,
                       trace_span, validate_event, validate_spec)


@task(inout="c", in_=("a", "b"))
def _gemm(c, a, b):
    return c + a @ b


def _gemm_run(executor, tracker, n=64, tile=32, **overrides):
    """The fixed gemm graph every determinism test uses: g=2, so 8 tasks
    in 2 wavefronts of 4 (one group each).  Returns (stats, result)."""
    g = n // tile
    rng = np.random.default_rng(0)
    a = rng.standard_normal((n, n), dtype=np.float32)
    b = rng.standard_normal((n, n), dtype=np.float32)
    with TaskRuntime(executor=executor, tracker=tracker,
                     n_workers=2, **overrides) as rt:
        A = rt.from_array(a, (tile, tile))
        B = rt.from_array(b, (tile, tile))
        C = rt.zeros((n, n), (tile, tile))
        for i in range(g):
            for j in range(g):
                for k in range(g):
                    _gemm(C[i, j], A[i, k], B[k, j])
        rt.barrier()
        stats = rt.stats()
        out = np.asarray(C.gather())
    return stats, out


# ---------------------------------------------------------------------------
class TestEventSchema:
    def test_schema_version_pinned(self):
        assert EVENT_SCHEMA == "repro-obs/1"

    def test_event_kinds_pinned(self):
        # removing/renaming a kind or a required key is a schema bump:
        # update EVENT_SCHEMA and this pin together
        assert set(EVENT_FIELDS) == {
            "trace_header", "wave_open", "wave_close", "dispatch",
            "kernel_dispatch", "queue_depth", "owner_override",
            "tile_cache", "sim_predict", "dep_msg", "dep_batch",
            "pump_idle", "manager_admit",
            "stats", "admission_admit", "admission_defer",
            "admission_reject", "admission_release",
            "ckpt_save", "ckpt_restore"}
        assert EVENT_FIELDS["admission_admit"] == {
            "request", "bytes", "in_flight_bytes"}
        assert EVENT_FIELDS["admission_reject"] == {
            "request", "bytes", "in_flight_bytes", "reason"}
        assert EVENT_FIELDS["ckpt_save"] == {
            "epoch", "arrays", "tiles", "bytes"}
        assert EVENT_FIELDS["kernel_dispatch"] == {
            "wave", "executor", "fn", "tasks", "backend", "reason"}
        assert EVENT_FIELDS["dep_msg"] == {"manager", "msg", "count"}
        assert EVENT_FIELDS["dep_batch"] == {
            "manager", "direction", "descriptors", "lines"}
        assert EVENT_FIELDS["pump_idle"] == {"manager", "waits"}
        assert EVENT_FIELDS["manager_admit"] == {
            "manager", "task", "deps", "depth"}
        assert EVENT_FIELDS["wave_close"] == {
            "wave", "executor", "tasks", "wall_s", "dispatches",
            "tile_moves", "bytes_moved", "bytes_staged"}
        assert EVENT_FIELDS["dispatch"] == {
            "wave", "executor", "fn", "tasks", "mode", "wall_s"}
        assert EVENT_FIELDS["queue_depth"] == {"channel", "depth"}

    def test_record_round_trip(self):
        ev = Event("dispatch", 0.25, {"wave": 1, "executor": "staged",
                                      "fn": "gemm", "tasks": 4,
                                      "mode": "vmap", "wall_s": 0.01})
        rec = ev.to_record()
        assert rec["kind"] == "dispatch" and rec["ts"] == 0.25
        back = Event.from_record(json.loads(ev.to_json()))
        assert back == ev

    def test_validate_event(self):
        ok = Event("wave_open", 0.0, {"wave": 1, "executor": "staged",
                                      "tasks": 4, "groups": 1})
        assert validate_event(ok) == []
        assert validate_event(Event("nope", 0.0, {}))        # unknown kind
        assert validate_event(Event("wave_open", 0.0, {}))   # missing keys
        assert validate_event(Event("wave_open", -1.0, ok.data))  # neg ts

    def test_every_emitted_event_validates(self):
        trk = InMemoryTracker()
        _gemm_run("staged", trk)
        assert trk.events
        for ev in trk.events:
            assert validate_event(ev) == [], ev


# ---------------------------------------------------------------------------
class TestTrackerSinks:
    def test_specs_and_validate_spec(self):
        for spec in ("none", "off", "memory", "console", "jsonl",
                     "jsonl:some/trace.jsonl"):
            validate_spec(spec)
        with pytest.raises(ValueError, match="tracker spec"):
            validate_spec("bogus")

    def test_make_tracker_ownership(self):
        t, owned = make_tracker(None)
        assert t is NULL_TRACKER and not owned
        t, owned = make_tracker("memory")
        assert isinstance(t, InMemoryTracker) and owned
        mine = InMemoryTracker()
        t, owned = make_tracker(mine)
        assert t is mine and not owned          # caller keeps instances
        with pytest.raises(TypeError):
            make_tracker(42)

    def test_null_tracker_satisfies_protocol(self):
        assert isinstance(NULL_TRACKER, Tracker)
        assert isinstance(InMemoryTracker(), Tracker)
        assert not NULL_TRACKER.enabled
        NULL_TRACKER.emit("wave_open", wave=1)   # all no-ops
        NULL_TRACKER.queue(0, 5)
        assert NULL_TRACKER.queue_depths() == {}

    def test_jsonl_sink_round_trips(self, tmp_path):
        path = tmp_path / "trace.jsonl"
        trk = JsonlTracker(str(path))
        _gemm_run("staged", trk)
        trk.close()
        events = load_jsonl(str(path))
        assert events[0].kind == "trace_header"
        assert events[0].data["schema"] == EVENT_SCHEMA
        assert trk.records_written == len(events)
        # identical timeline shape to the in-memory sink on the same graph
        mem = InMemoryTracker()
        _gemm_run("staged", mem)
        kinds = [e.kind for e in events if e.kind != "trace_header"]
        assert kinds == [e.kind for e in mem.events]

    def test_console_sink_summarizes(self):
        import io
        out = io.StringIO()
        trk = ConsoleTracker(out=out)
        _gemm_run("staged", trk)
        trk.close()
        text = out.getvalue()
        assert "[obs]" in text and "waves" in text
        assert "slowest" in text

    def test_caller_owned_tracker_stays_open(self):
        trk = InMemoryTracker()
        _gemm_run("staged", trk)
        assert not trk._closed            # runtime must not close it
        _gemm_run("staged", trk)          # reusable across runtimes
        assert len(trk.events_of("stats")) == 2

    def test_double_shutdown_emits_once(self):
        trk = InMemoryTracker()
        rt = TaskRuntime(executor="staged", tracker=trk)
        rt.shutdown()
        rt.shutdown()
        assert len(trk.events_of("stats")) == 1


# ---------------------------------------------------------------------------
class TestDeterministicCounts:
    """Fixed gemm graph (8 tasks, 2 waves of 4): event counts are exact."""

    def test_staged_timeline(self):
        trk = InMemoryTracker()
        stats, _ = _gemm_run("staged", trk)
        opens = trk.events_of("wave_open")
        closes = trk.events_of("wave_close")
        assert len(opens) == len(closes) == stats.waves == 2
        assert [e.data["tasks"] for e in opens] == [4, 4]
        assert all(e.data["executor"] == "staged" for e in opens + closes)
        dispatches = trk.events_of("dispatch")
        assert len(dispatches) == 2                 # one group per wave
        assert [e.data["mode"] for e in dispatches] == ["vmap", "vmap"]
        assert sum(e.data["dispatches"] for e in closes) == len(dispatches)
        assert all(e.data["wall_s"] >= 0 for e in closes + dispatches)
        # wave open/close pair up in order, with close after open
        for o, c in zip(opens, closes):
            assert o.data["wave"] == c.data["wave"]
            assert c.ts >= o.ts
        # queue accounting drains back to zero on channel 0
        assert trk.queue_depths() == {0: 0}

    def test_wave_traffic_sums_to_stats(self):
        trk = InMemoryTracker()
        stats, _ = _gemm_run("staged", trk)
        closes = trk.events_of("wave_close")
        assert sum(e.data["bytes_moved"] for e in closes) == \
            stats.bytes_moved
        assert sum(e.data["tile_moves"] for e in closes) == stats.tile_moves
        assert sum(e.data["bytes_staged"] for e in closes) == \
            stats.bytes_staged == 0

    def test_sharded_timeline_single_device(self):
        trk = InMemoryTracker()
        stats, out = _gemm_run("sharded", trk)
        closes = trk.events_of("wave_close")
        assert len(closes) == 2
        assert all(e.data["executor"] == "sharded" for e in closes)
        # per-home queue channels all drain to zero
        depths = trk.queue_depths()
        assert depths and all(d == 0 for d in depths.values())

    def test_host_queue_and_cache_events(self):
        trk = InMemoryTracker()
        stats, _ = _gemm_run("host", trk, worker_cache_tiles=8)
        # every scheduled task enqueues once and collects once
        qd = trk.events_of("queue_depth")
        assert len(qd) == 2 * stats.tasks_scheduled == 16
        assert all(d == 0 for d in trk.queue_depths().values())
        cache = trk.events_of("tile_cache")
        assert len(cache) == 2                      # one per worker
        hits = sum(e.data["hits"] for e in cache)
        misses = sum(e.data["misses"] for e in cache)
        assert hits == sum(stats.worker_cache_hits)
        assert misses == sum(stats.worker_cache_misses)
        # 8 tasks x 3 READS regions = 24 lookups in total
        assert hits + misses == 24
        assert hits > 0                              # A/B tiles repeat

    def test_sequential_emits_stats_only(self):
        trk = InMemoryTracker()
        _gemm_run("sequential", trk)
        assert {e.kind for e in trk.events} == {"stats"}

    def test_sim_predict_event(self):
        trk = InMemoryTracker()
        stats, _ = _gemm_run("sim", trk)
        (ev,) = trk.events_of("sim_predict")
        assert ev.data["tasks"] == 8
        assert ev.data["predicted_s"] == pytest.approx(
            stats.predicted_total_s)
        assert ev.data["predicted_s"] > 0
        assert ev.data["sequential_s"] > 0

    def test_stats_event_round_trips(self):
        trk = InMemoryTracker()
        stats, _ = _gemm_run("staged", trk)
        (ev,) = trk.events_of("stats")
        # the payload is the shutdown-time snapshot (taken after the exit
        # barrier, so wall-clock fields drift past the mid-run copy) in
        # the to_dict schema: it parses, and every deterministic counter
        # matches the stats() the program saw
        got = RuntimeStats.from_dict(ev.data["stats"])
        assert got.group_program_tasks
        assert got.group_operand_tiles and got.group_distinct_tiles
        assert (got.split_programs, got.tiles_split) == (2, 8)
        for f in ("tasks_spawned", "deps_found", "waves",
                  "grouped_dispatches", "group_program_tasks",
                  "group_operand_tiles", "group_distinct_tiles",
                  "tile_moves", "bytes_moved",
                  "bytes_staged", "split_programs", "tiles_split",
                  "region_waits", "futures_resolved"):
            assert getattr(got, f) == getattr(stats, f), f


# ---------------------------------------------------------------------------
class TestDisabledTrackerIsFree:
    def test_no_tracker_means_no_emit_calls(self):
        """The zero-overhead guarantee: with the default NULL_TRACKER the
        hot path never even calls emit/queue (every site is guarded by
        ``obs.enabled``) — proven by a spy, not a wall clock."""
        calls = []

        class Spy(NullTracker):            # enabled stays False
            def emit(self, kind, **data):
                calls.append(kind)

            def queue(self, channel, delta):
                calls.append("queue")

        spy = Spy()
        for executor in ("staged", "sharded", "host", "sim", "sequential"):
            _gemm_run(executor, spy)
        assert calls == []

    def test_default_config_has_no_tracker(self):
        assert RuntimeConfig().tracker is None
        rt = TaskRuntime(executor="staged")
        assert rt.obs is NULL_TRACKER
        rt.shutdown()

    def test_config_rejects_bad_tracker(self):
        with pytest.raises(ValueError, match="tracker spec"):
            RuntimeConfig(tracker="bogus").validate()
        with pytest.raises(ValueError, match="tracker"):
            RuntimeConfig(tracker=42).validate()
        with pytest.raises(ValueError, match="worker_cache_tiles"):
            RuntimeConfig(worker_cache_tiles=-1).validate()


# ---------------------------------------------------------------------------
class TestChromeTrace:
    def _events(self):
        trk = InMemoryTracker()
        _gemm_run("staged", trk)
        return trk.events

    def test_chrome_trace_is_valid(self, tmp_path):
        doc = chrome_trace(self._events())
        # valid trace JSON: object format with a traceEvents list
        parsed = json.loads(json.dumps(doc))
        evs = parsed["traceEvents"]
        assert evs
        for e in evs:
            assert e["ph"] in ("X", "C", "i", "M")
            if e["ph"] != "M":
                assert e["ts"] >= 0
        # wave spans and dispatch spans both present, with durations
        spans = [e for e in evs if e["ph"] == "X"]
        assert any(e["name"].startswith("wave ") for e in spans)
        assert any("[staged]" in e["name"] for e in spans)
        assert all(e["dur"] >= 0 for e in spans)
        counters = [e for e in evs if e["ph"] == "C"]
        assert counters and all("depth" in e["args"] for e in counters)

    def test_timestamps_monotonic(self):
        evs = chrome_trace(self._events())["traceEvents"]
        ts = [e["ts"] for e in evs if e["ph"] != "M"]
        assert ts == sorted(ts)

    def test_export_from_jsonl_path(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        trk = JsonlTracker(str(trace))
        _gemm_run("staged", trk)
        trk.close()
        out = tmp_path / "t.json"
        doc = export_chrome_trace(str(trace), str(out))
        assert json.loads(out.read_text())["traceEvents"] == \
            doc["traceEvents"]

    def test_cli_summary_and_chrome(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        trk = JsonlTracker(str(trace))
        _gemm_run("staged", trk)
        trk.close()
        repo = pathlib.Path(__file__).resolve().parent.parent
        env = {**os.environ, "PYTHONPATH": "src"}
        out = subprocess.run(
            [sys.executable, "-m", "repro.obs", "summary", str(trace),
             "--top", "3"],
            capture_output=True, text=True, cwd=repo, timeout=120,
            env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "| wave |" in out.stdout
        chrome_out = tmp_path / "t.json"
        out = subprocess.run(
            [sys.executable, "-m", "repro.obs", "chrome", str(trace),
             "-o", str(chrome_out)],
            capture_output=True, text=True, cwd=repo, timeout=120,
            env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        assert json.loads(chrome_out.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
class TestSummary:
    def test_slowest_waves_orders_by_wall(self):
        evs = [Event("wave_close", float(i),
                     {"wave": i, "executor": "staged", "tasks": 1,
                      "wall_s": w, "dispatches": 1, "tile_moves": 0,
                      "bytes_moved": 0, "bytes_staged": 0})
               for i, w in enumerate([0.1, 0.5, 0.2])]
        top = slowest_waves(evs, top=2)
        assert [e.data["wave"] for e in top] == [1, 2]

    def test_summary_table_shape(self):
        trk = InMemoryTracker()
        _gemm_run("staged", trk)
        table = summary_table(trk.events, top=5)
        assert "**trace**" in table
        assert "| wave | executor |" in table
        assert table.count("\n| ") >= 3       # header sep + 2 wave rows

    def _dispatch(self, mode, wall):
        return Event("dispatch", 0.0,
                     {"wave": 0, "executor": "staged", "fn": "f",
                      "tasks": 1, "mode": mode, "wall_s": wall})

    def test_mode_latency_percentiles(self):
        # 100 jit dispatches at 1..100ms: nearest-rank p50=50ms p99=99ms
        evs = [self._dispatch("jit", i / 1000) for i in range(1, 101)]
        evs.append(self._dispatch("vmap", 0.5))
        hist = mode_latency(evs)
        assert list(hist) == ["jit", "vmap"]      # sorted by mode
        assert hist["jit"]["count"] == 100
        assert hist["jit"]["p50_s"] == pytest.approx(0.050)
        assert hist["jit"]["p99_s"] == pytest.approx(0.099)
        assert hist["vmap"] == {"count": 1, "total_s": 0.5,
                                "p50_s": 0.5, "p99_s": 0.5}

    def test_mode_latency_in_summary_table(self):
        trk = InMemoryTracker()
        _gemm_run("staged", trk)
        table = summary_table(trk.events, top=5)
        assert "| mode | dispatches |" in table
        modes = mode_latency(trk.events)
        assert modes                              # staged run dispatched
        assert sum(h["count"] for h in modes.values()) \
            == len(trk.events_of("dispatch"))

    def test_mode_latency_empty_without_dispatches(self):
        assert mode_latency([]) == {}
        assert "| mode |" not in summary_table([])


# ---------------------------------------------------------------------------
class TestProfilerHook:
    def test_trace_span_disabled_is_nullcontext(self):
        with trace_span("x", False):
            pass

    def test_trace_span_enabled_runs(self):
        # TraceAnnotation works outside an active profiler session
        with trace_span("bddt/test/wave1", True):
            pass

    @pytest.mark.parametrize("executor", ["staged", "sharded"])
    @pytest.mark.parametrize("profile", [True, False], ids=["on", "off"])
    def test_profile_waves_config_plumbs(self, executor, profile):
        """``profile_waves`` alone, with no tracker, opens the program
        spans: the analysis per task, and per wave the layering, each
        group's stack, call and store, and the release.  The steps are
        leaves under their wave (or at the barrier's top level), and each
        wave has one stack, call and store span per group dispatched,
        each call span holding that group's jitted body calls.  Off, no
        annotation is built at all.  The sharded case runs on a forced
        2-device mesh, where an uneven group makes one call per device."""
        if executor == "staged":
            got = _record_spans(executor, profile)
        else:
            got = _record_spans_on_mesh(executor, profile)
        log = got["log"]
        spans = [(i, e[1], e[2]) for i, e in enumerate(log) if e[0] == "span"]
        calls = [e[1] for e in log if e[0] == "dispatch"]
        assert got["profile"] is profile
        assert calls                                    # the body ran
        if not profile:
            assert spans == []
            return
        kind = f"bddt/{executor}"
        name = {i: n for i, n, _ in spans}
        waves = [i for i, n, _ in spans if n.startswith(f"{kind}/wave")]
        assert len({name[w] for w in waves}) == len(waves) == got["waves"]
        assert {n for _, n, _ in spans} == {
            "bddt/analyze", *(name[w] for w in waves),
            *(f"{kind}/{s}" for s in ("layer", "stack", "call", "store",
                                      "release"))}
        for i, n, parent in spans:
            # waves at the top; every step a leaf, in a wave or at the top
            assert parent is None or (i not in waves and parent in waves)
        assert [n for _, n, _ in spans].count("bddt/analyze") == got["tasks"]
        # every body call inside a call span, directly in a wave
        assert all(name[c] == f"{kind}/call" for c in calls)
        n_groups = 0
        for w in waves:
            inside = [n for _, n, p in spans if p == w]
            in_call = {i for i, n, p in spans
                       if p == w and n == f"{kind}/call"}
            assert in_call and in_call <= set(calls), name[w]
            for s in ("stack", "call", "store"):
                assert inside.count(f"{kind}/{s}") == len(in_call), \
                    (name[w], s)
            assert inside.count(f"{kind}/layer") == 1
            assert inside.count(f"{kind}/release") == 1
            n_groups += len(in_call)
        if executor == "staged":                  # one call per group
            assert len(calls) == n_groups
        else:                                     # uneven groups split
            assert len(calls) > n_groups
        top = [n for _, n, p in spans if p is None and n != "bddt/analyze"]
        assert top.count(f"{kind}/layer") == top.count(f"{kind}/release") \
            == 1                                    # one barrier


def _record_spans(executor: str, profile: bool) -> dict:
    """Two 4x4-tile Cholesky solves on one runtime with no tracker; the
    second is recorded: each annotation as ``["span", name, parent]``
    through a stand-in for ``TraceAnnotation``, and each jitted body call
    as ``["dispatch", innermost open span]`` by wrapping the executor's
    compiled bodies (cached by the first solve); a span is named by its
    place in the log."""
    from benchmarks.apps import cholesky_app
    from repro.obs import profiler

    log, stack = [], []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            stack.append(len(log))
            log.append(["span", self.name, stack[-2] if stack[1:] else None])

        def __exit__(self, *exc):
            stack.pop()

    def counted(fn):
        def call(*args):
            log.append(["dispatch", stack[-1] if stack else None])
            return fn(*args)
        return call

    real = profiler.TraceAnnotation
    profiler.TraceAnnotation = Recorder
    try:
        rt = TaskRuntime(executor=executor, profile_waves=profile,
                         n_controllers=2)
        cholesky_app(rt, n=64, tile=16)
        ex = rt._exec
        for cache in (ex._jit, ex._vjit, getattr(ex, "_smap", {})):
            for key in cache:
                cache[key] = counted(cache[key])
        before = rt.stats()
        del log[:]
        cholesky_app(rt, n=64, tile=16)
        after = rt.stats()
        rt.shutdown()
    finally:
        profiler.TraceAnnotation = real
    return {"log": log, "profile": ex.profile,
            "tasks": after.tasks_spawned - before.tasks_spawned,
            "waves": after.waves - before.waves}


def _record_spans_on_mesh(executor: str, profile: bool) -> dict:
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json, sys
sys.path[:0] = ["src", ".", "tests"]
import jax, numpy as np
from repro import dist
import test_obs
assert jax.device_count() == 2
mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("data",))
with dist.use_mesh(mesh):
    print(json.dumps(test_obs._record_spans({executor!r}, {profile!r})))
"""
    out = subprocess.run([sys.executable, "-c", code],
                         cwd=pathlib.Path(__file__).resolve().parent.parent,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
class TestStatsRoundTrip:
    def test_json_round_trip_exact(self):
        stats, _ = _gemm_run("staged", None)
        d = stats.to_dict()
        assert d["schema"] == STATS_SCHEMA
        # A and B were each tiled by one split program (2 x 4 tiles)
        assert (d["split_programs"], d["tiles_split"]) == (2, 8)
        assert RuntimeStats.from_json(stats.to_json()) == stats

    def test_round_trip_with_worker_fields(self):
        stats, _ = _gemm_run("host", None, worker_cache_tiles=4)
        assert stats.worker_cache_hits is not None
        assert RuntimeStats.from_json(stats.to_json()) == stats

    def test_from_dict_rejects_bad_schema_and_fields(self):
        stats, _ = _gemm_run("sequential", None)
        d = stats.to_dict()
        with pytest.raises(ValueError, match="schema"):
            RuntimeStats.from_dict({**d, "schema": "nope/9"})
        with pytest.raises(ValueError, match="unknown"):
            RuntimeStats.from_dict({**d, "mystery_field": 1})

    def test_report_table_accepts_dicts(self):
        from benchmarks.report import runtime_stats_table
        stats, _ = _gemm_run("staged", None)
        a = runtime_stats_table([("gemm", stats)])
        b = runtime_stats_table([("gemm", stats.to_dict())])
        c = runtime_stats_table([("gemm", stats.to_json())])
        assert a == b == c


# ---------------------------------------------------------------------------
class TestWorkerTileCache:
    def test_cache_disabled_by_default_in_executor(self):
        from repro.core.executor import _Worker
        from repro.core.mpb import MPBQueue
        w = _Worker(0, MPBQueue(0, 4))
        assert w.cache_tiles == 0

    def test_cache_off_means_no_counters(self):
        stats, _ = _gemm_run("host", None, worker_cache_tiles=0)
        assert stats.worker_cache_hits == [0, 0]
        assert stats.worker_cache_misses == [0, 0]

    def test_cache_correct_under_overwrites(self):
        """The gemm InOut region C[i,j] is re-read after every overwrite:
        the cache must miss on changed tiles (object identity) and still
        produce bit-identical results."""
        _, ref_out = _gemm_run("sequential", None)
        stats, out = _gemm_run("host", None, worker_cache_tiles=64)
        np.testing.assert_array_equal(out, ref_out)
        assert sum(stats.worker_cache_hits) > 0

    def test_lru_eviction_bounds_cache(self):
        from collections import OrderedDict
        from repro.core.executor import _Worker
        from repro.core.mpb import MPBQueue
        from repro.core.blocks import BlockArray
        w = _Worker(0, MPBQueue(0, 4), cache_tiles=2)
        ba = BlockArray.from_array(
            np.arange(64, dtype=np.float32).reshape(8, 8), (2, 2))
        regions = [ba[i, j] for i in range(2) for j in range(2)]
        for r in regions:
            w._materialize(r)
        assert len(w._cache) == 2                   # LRU evicted
        assert w.cache_misses == 4 and w.cache_hits == 0
        np.testing.assert_array_equal(
            np.asarray(w._materialize(regions[-1])),
            np.asarray(regions[-1].materialize()))
        assert w.cache_hits == 1


# ---------------------------------------------------------------------------
class TestQueueFedRebalance:
    def test_zero_base_equals_wave_local(self):
        """base_load=None and base_load=zeros are the same decision on
        every wave shape — the equivalence the sharded feedback hinges
        on (an unskewed tracker contributes a balanced base)."""
        waves = [[0, 1, 2, 3], [0, 0, 0, 0], [0, 0, 1, 2, 3, 3, 3, 3],
                 [2], []]
        for owners in waves:
            for thr in (0.0, 1.2, 1.5, 2.0):
                legacy = rebalance_owners(list(owners), 4, thr)
                fed = rebalance_owners(list(owners), 4, thr,
                                       base_load=[0.0] * 4)
                assert legacy == fed, (owners, thr)

    def test_balanced_base_no_extra_spill(self):
        # a uniformly-loaded background shifts every home equally: the
        # skew ratio only moves toward the mean, so an unskewed wave
        # stays unspilled
        owners = [0, 1, 2, 3, 0, 1, 2, 3]
        for base in ([0.0] * 4, [5.0] * 4):
            got, spilled = rebalance_owners(list(owners), 4, 1.5,
                                            base_load=base)
            assert got == owners and spilled == 0

    def test_background_hot_home_stops(self):
        # home 3 is hot purely on background load: nothing of this
        # group's to move, must terminate without spilling
        got, spilled = rebalance_owners([0, 0, 1, 2], 4, 1.1,
                                        base_load=[0, 0, 0, 100])
        assert spilled == 0 and got == [0, 0, 1, 2]

    def test_base_load_validation(self):
        with pytest.raises(ValueError, match="one entry per home"):
            rebalance_owners([0], 4, 1.5, base_load=[1.0, 2.0])
        with pytest.raises(ValueError, match=">= 0"):
            rebalance_owners([0], 4, 1.5, base_load=[1, -1, 0, 0])

    def test_sharded_with_tracker_matches_without(self):
        """Queue-depth-fed rebalance on unskewed waves: identical results
        and overrides with the tracker on or off."""
        s_off, out_off = _gemm_run("sharded", None,
                                   owner_skew_threshold=1.5)
        trk = InMemoryTracker()
        s_on, out_on = _gemm_run("sharded", trk, owner_skew_threshold=1.5)
        np.testing.assert_array_equal(out_off, out_on)
        assert s_on.owner_overrides == s_off.owner_overrides
        assert s_on.bytes_staged == s_off.bytes_staged == 0
        assert s_on.cross_home_bytes == s_off.cross_home_bytes


# ---------------------------------------------------------------------------
def _load_gate():
    import importlib.util
    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "bench_gate_obs", root / "tools" / "bench_gate.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchTimings:
    def test_validate_timings(self):
        gate = _load_gate()
        timings_point = gate.timings_point
        validate_timings = gate.validate_timings
        assert validate_timings({}) == []           # block is optional
        good = {"timings": {"schema": "bddt-scc-timings/1",
                            "suite": "smoke", "suite_wall_s": 1.5,
                            "spawn_us_per_task": 40.0,
                            "staged_wall_s": {"matmul": 0.2}}}
        assert validate_timings(good) == []
        pt = timings_point({**good, "env": {"jax": "x"}})
        assert pt["staged_wall_s"] == {"matmul": 0.2}
        assert pt["env"] == {"jax": "x"}
        bad = json.loads(json.dumps(good))
        bad["timings"]["suite_wall_s"] = float("nan")
        assert validate_timings(bad)
        bad = json.loads(json.dumps(good))
        bad["timings"]["staged_wall_s"] = {}
        assert validate_timings(bad)
        bad = json.loads(json.dumps(good))
        bad["timings"]["schema"] = "nope"
        assert validate_timings(bad)

    def test_gate_appends_timings(self, tmp_path):
        gate_main = _load_gate().main
        doc = {"schema": "bddt-scc-bench/1", "suite": "smoke",
               "wall_s": 1.0, "env": {}, "calibration": {},
               "entries": [{"id": "x", "kind": "app", "info": {},
                            "metrics": {"tasks": 8}}],
               "timings": {"schema": "bddt-scc-timings/1",
                           "suite": "smoke", "suite_wall_s": 1.0,
                           "spawn_us_per_task": 10.0,
                           "staged_wall_s": {"matmul": 0.1}},
               "validation": {"checks": {}, "passed": 0, "total": 0}}
        art = tmp_path / "BENCH.json"
        art.write_text(json.dumps(doc))
        series = tmp_path / "series.jsonl"
        base = tmp_path / "base.json"
        # twice: series is append-only, one JSON line per run
        for _ in range(2):
            rc = gate_main([str(art), "--baseline", str(base),
                            "--append-timings", str(series)])
            assert rc == 0
        lines = series.read_text().strip().splitlines()
        assert len(lines) == 2
        assert json.loads(lines[0])["suite_wall_s"] == 1.0

    def test_run_builds_timings_block(self):
        # the emitter and the gate agree on the timings schema tag
        from benchmarks.run import TIMINGS_SCHEMA
        assert TIMINGS_SCHEMA == _load_gate().TIMINGS_SCHEMA \
            == "bddt-scc-timings/1"


# ---------------------------------------------------------------------------
def test_two_device_wave_timeline():
    """The ISSUE 6 acceptance run: on a forced-host 2-device mesh, one
    staged and one sharded gemm run each emit a complete wave timeline
    through in-memory and JSONL sinks — per-wave tile-move bytes sum to
    ``RuntimeStats.bytes_moved``, the Chrome export is valid, and the
    queue-depth-fed owner override keeps ``bytes_staged == 0`` with
    bit-identical results."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import json
import jax, numpy as np
from repro import dist
from repro.core import TaskRuntime, task
from repro.obs import (InMemoryTracker, JsonlTracker, chrome_trace,
                       load_jsonl, validate_event)

assert jax.device_count() == 2
mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(2), ("data",))

@task(inout="c", in_=("a", "b"))
def gemm(c, a, b):
    return c + a @ b

rng = np.random.default_rng(0)
a = rng.standard_normal((128, 128), dtype=np.float32)
b = rng.standard_normal((128, 128), dtype=np.float32)

def prog(executor, tracker, **overrides):
    g = 4
    with TaskRuntime(executor=executor, tracker=tracker,
                     n_controllers=2, **overrides) as rt:
        A = rt.from_array(a, (32, 32)); B = rt.from_array(b, (32, 32))
        C = rt.zeros((128, 128), (32, 32))
        for i in range(g):
            for j in range(g):
                for k in range(g):
                    gemm(C[i, j], A[i, k], B[k, j])
        rt.barrier()
        s = rt.stats()
        return np.asarray(C.gather()), s

def check_timeline(trk, stats, executor):
    closes = trk.events_of("wave_close")
    opens = trk.events_of("wave_open")
    assert len(opens) == len(closes) == 4, (executor, len(closes))
    assert all(e.data["executor"] == executor for e in closes)
    assert all(e.data["wall_s"] >= 0 for e in closes)
    assert trk.events_of("dispatch"), executor
    assert trk.events_of("queue_depth"), executor
    assert all(d == 0 for d in trk.queue_depths().values()), executor
    # per-wave measured movement sums exactly to the stats totals
    assert sum(e.data["bytes_moved"] for e in closes) == \
        stats.bytes_moved, executor
    assert sum(e.data["bytes_staged"] for e in closes) == 0, executor
    for ev in trk.events:
        assert validate_event(ev) == [], ev

ref, _ = prog("sequential", None)

trk = InMemoryTracker()
got, s = prog("staged", trk)
np.testing.assert_array_equal(ref, got)
check_timeline(trk, s, "staged")

with dist.use_mesh(mesh):
    trk = InMemoryTracker()
    got, s = prog("sharded", trk)
    np.testing.assert_array_equal(ref, got)
    check_timeline(trk, s, "sharded")
    assert s.bytes_moved > 0            # real cross-device movement
    assert s.bytes_staged == 0

    # JSONL sink on the same program, then the Chrome export of it
    jt = JsonlTracker("obs_trace_test.jsonl")
    got, s = prog("sharded", jt)
    jt.close()
    events = load_jsonl("obs_trace_test.jsonl")
    assert events[0].kind == "trace_header"
    assert sum(e.data["bytes_moved"] for e in events
               if e.kind == "wave_close") == s.bytes_moved
    doc = chrome_trace(events)
    ts = [e["ts"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert ts and ts == sorted(ts) and min(ts) >= 0
    os.unlink("obs_trace_test.jsonl")

    # queue-depth-fed owner override: unskewed gemm waves place the
    # same with and without the tracker feeding base load
    got_off, s_off = prog("sharded", None, owner_skew_threshold=1.5)
    got_on, s_on = prog("sharded", InMemoryTracker(),
                        owner_skew_threshold=1.5)
    np.testing.assert_array_equal(got_off, got_on)
    np.testing.assert_array_equal(ref, got_on)
    assert s_on.owner_overrides == s_off.owner_overrides
    assert s_on.bytes_staged == s_off.bytes_staged == 0
    assert s_on.bytes_moved == s_off.bytes_moved

print("OBS-2DEV-OK")
"""
    out = subprocess.run([sys.executable, "-c", code],
                         cwd=pathlib.Path(__file__).resolve().parent.parent,
                         capture_output=True, text=True, timeout=300)
    assert "OBS-2DEV-OK" in out.stdout, out.stderr[-3000:]
