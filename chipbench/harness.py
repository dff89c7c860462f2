"""One benchmark run: set-up, the measured window of solves, the check.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration file (sizes, limits and the name of
its program), the program's ``@task`` code in ``chipbench/programs/``, its
inputs, reference and kernel costs in ``chipbench/refs/``, its traffic in
``chipbench/traffic/<name>.json`` (tile, executor, placement, mesh) and
each per-layer metric's reader in ``chipbench/metrics/<name>.py``.

Set-up turns the compilation cache on, makes the inputs on the device from
the seed, builds one ``TaskRuntime`` and runs one warm-up solve on it.  A
solve tiles the device-resident inputs into the runtime's arrays, spawns
the whole program, calls ``rt.barrier()`` and waits for every output tile.
The window runs solves back to back on that runtime: they start while the
window is open, and the last one runs to its end.  Once it has closed, the
runtime is freed and a seeded sample of the window's solves is compared
with the plain reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import random
import sys
import time
from collections import defaultdict
from pathlib import Path

from chipbench import record, tracing

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"

#: solves of the window whose outputs are kept for the check
SAMPLE_SOLVES = 2

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


# -- the cell, found by name ----------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def program(self):
        return importlib.import_module(
            f"chipbench.programs.{self.config['program']}")

    @property
    def ref(self):
        return importlib.import_module(
            f"chipbench.refs.{self.config['program']}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r}; "
                         f"known: {sorted(cells)}")
    return make_cell(cells[name], bench)


def make_cell(w: dict, bench: dict) -> Cell:
    """The cell of workload entry ``w``: its configuration is
    ``chipbench/configs/<config>.json``, its traffic
    ``chipbench/traffic/<traffic>.json``."""
    config = json.loads((HERE / "configs" / f"{w['config']}.json")
                        .read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    name = w["name"]
    return Cell(name, w["chips"], config, traffic,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def _reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- process-wide set-up ---------------------------------------------------------
def enable_compile_cache() -> str:
    """JAX's persistent compilation cache for every program, however short
    its compile: ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    directory ``<checkout>/.jax_cache``."""
    import os

    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX's backend compiles (a program compiled, or loaded from
    the persistent cache) and cache hits, per phase of the run."""

    def __init__(self):
        self.phase = "setup"
        self.compiles = defaultdict(int)
        self.compile_s = defaultdict(float)
        self.cache_hits = defaultdict(int)

    def install(self) -> "CompileCounter":
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)
        return self

    def _duration(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.compiles[self.phase] += 1
            self.compile_s[self.phase] += duration

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits[self.phase] += 1


def seed_key(seed: int):
    """A PRNG key from every bit of a seed of up to 64 bits."""
    import jax
    s = seed % 2 ** 64
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


class Spans:
    """Host seconds per harness span; with ``annotate`` each span is also a
    ``jax.profiler.TraceAnnotation`` in the device trace."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.seconds = defaultdict(float)

    @contextlib.contextmanager
    def __call__(self, name: str):
        from jax.profiler import TraceAnnotation
        ann = TraceAnnotation(name) if self.annotate else \
            contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds[name] += time.perf_counter() - t0


class _SpansOnly:
    """An enabled runtime tracker that records nothing: with it and
    ``profile_waves`` the executors annotate every wave in the trace."""

    enabled = True

    def emit(self, kind, **data):
        pass

    def queue(self, channel, delta):
        pass

    def queue_depths(self):
        return {}

    def close(self):
        pass


def _mesh(devices, n: int):
    if not n:
        return contextlib.nullcontext()
    import numpy as np
    from jax.sharding import Mesh
    from repro import dist
    return dist.use_mesh(Mesh(np.asarray(devices[:n]), ("data",)))


def _memory(devices, key: str) -> int | None:
    vals = []
    for d in devices:
        stats = d.memory_stats()
        if stats is None or key not in stats:
            return None
        vals.append(stats[key])
    return max(vals)


# -- the run ---------------------------------------------------------------------
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_process: float, counter: CompileCounter,
             log=sys.stderr) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax
    from repro import RuntimeConfig, TaskRuntime

    cfg, traffic = cell.config, cell.traffic
    tile = traffic["tile"]
    grid = cfg["n"] // tile
    prog, ref = cell.program, cell.ref
    n_dev = max(traffic["mesh_devices"], 1)
    used = devices[:n_dev]
    kind = used[0].device_kind

    # -- set-up: inputs, runtime, warm-up solve
    t0 = time.perf_counter()
    inputs = ref.make_inputs(seed_key(seed), cfg)
    jax.block_until_ready(inputs)
    t_data = time.perf_counter() - t0
    spans = Spans(annotate=trace)
    out_idx = ref.output_indices(grid)
    moved = [0]

    with _mesh(devices, traffic["mesh_devices"]):
        rt = TaskRuntime(RuntimeConfig(
            executor=traffic["executor"], placement=traffic["placement"],
            n_controllers=traffic["homes"],
            tracker=_SpansOnly() if trace else None, profile_waves=trace))
        arrays: dict = {}

        def solve() -> dict:
            with spans("reload"):
                if not arrays:
                    arrays.update({k: rt.from_array(inputs[k], (tile, tile),
                                                    name=k)
                                   for k in ref.INPUTS})
                else:
                    for k in ref.INPUTS:
                        arrays[k].scatter(inputs[k])
            before = rt.traffic.bytes_moved
            with spans("spawn"), rt.scope():
                prog.spawn(arrays, grid)
            with spans("barrier"):
                rt.barrier()
            with spans("drain"):
                out = arrays[prog.OUTPUT]
                tiles = {i: out.get_tile(i) for i in out_idx}
                jax.block_until_ready(list(tiles.values()))
            moved[0] += rt.traffic.bytes_moved - before
            return tiles

        t1 = time.perf_counter()
        solve()
        t_warm = time.perf_counter() - t1
        tasks0, waves0 = rt.stats().tasks_spawned, rt.stats().waves
        spans.seconds.clear()
        moved[0] = 0
        mem_setup = _memory(used, "bytes_in_use")

        # -- the window
        rng = random.Random(seed)
        sample: list = []
        solve_s: list = []
        mem_after: list = []
        traces: list = []
        counter.phase = "window"
        with (tracing.capture(traces) if trace
              else contextlib.nullcontext()):
            with spans(tracing.WINDOW):
                t_start = time.perf_counter()
                setup_s = t_start - t_process
                deadline = t_start + seconds
                t = t_start
                while t < deadline:
                    tiles = solve()
                    i = len(solve_s)
                    if i < SAMPLE_SOLVES:
                        sample.append((i, tiles))
                    else:
                        j = rng.randrange(i + 1)
                        if j < SAMPLE_SOLVES:
                            sample[j] = (i, tiles)
                    del tiles
                    now = time.perf_counter()
                    solve_s.append(now - t)
                    t = now
                    mem_after.append(_memory(used[:1], "bytes_in_use"))
                t_end = t
        counter.phase = "after"
        solves = len(solve_s)
        stats = rt.stats()
        tasks = stats.tasks_spawned - tasks0
        waves_per_solve = max(waves0, 1)
        peak = _memory(used, "peak_bytes_in_use")
        rt.shutdown()
        del rt, arrays
        gc.collect()

    window_s = t_end - t_start
    metrics = {}
    if not trace:
        metrics["solve_s"] = {"value": window_s / solves, "unit": "s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    rec = record.Record(
        cell=cell.name, config=cfg, traffic=traffic, device_kind=kind,
        n_devices=n_dev, solves=solves, tasks=tasks,
        spans=dict(spans.seconds),
        compiles_in_window=counter.compiles["window"],
        bytes_moved=moved[0], kernels=ref.kernel_costs(cfg, tile),
        trace=traces[0] if traces else None)
    if trace:
        for m in cell.per_layer:
            value = _reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": used[0].platform, "kind": kind, "count": n_dev,
              "memory_peak_bytes": peak}
    breakdown = None
    if rec.trace is not None:
        device["busy_s"] = tracing.busy_seconds(rec.trace) or 0.0
        device["window_s"] = rec.trace.window_s

        def per_solve_wave(name: str) -> str:
            head, _, n = name.rpartition("wave")
            if head.startswith("bddt/") and n.isdigit():
                return f"{head}wave{(int(n) - 1) % waves_per_solve + 1}"
            return name

        breakdown = {
            "device_ops": tracing.top_programs(rec.trace),
            "idle_gaps": tracing.idle_by_host_span(rec.trace,
                                                   per_solve_wave)}

    # -- the check, once the runtime is freed
    t2 = time.perf_counter()
    want = ref.reference(inputs, cfg)
    readings: dict = defaultdict(list)
    for i, tiles in sample:
        for k, v in ref.compare(tiles, want, tile).items():
            readings[k].append((v, i))
    checks, failed_solves = {}, set()
    for k, limit in cfg["limits"].items():
        got = readings.get(k, [])
        worst = max(got, default=(float("nan"), -1))
        checks[k] = {"value": worst[0], "limit": limit}
        failed_solves.update(i for v, i in got if not v <= limit)
    correct = bool(sample) and all(
        c["value"] <= c["limit"] for c in checks.values())
    t_check = time.perf_counter() - t2

    print(json.dumps({
        "cell": cell.name, "seed": seed, "solves": solves,
        "tasks_per_solve": tasks // max(solves, 1),
        "waves_per_solve": waves_per_solve,
        "setup": {"data_s": t_data, "warmup_s": t_warm,
                  "compiles": counter.compiles["setup"],
                  "compile_s": counter.compile_s["setup"],
                  "cache_hits": counter.cache_hits["setup"],
                  "bytes_in_use": mem_setup},
        "window": {"spans_s": dict(spans.seconds),
                   "compiles": counter.compiles["window"],
                   "solve_s_first_last": [solve_s[0], solve_s[-1]],
                   "solve_s_min_max": [min(solve_s), max(solve_s)],
                   "bytes_in_use_first_last": [mem_after[0], mem_after[-1]],
                   "bytes_moved": moved[0]},
        "check": {"sampled_solves": sorted(i for i, _ in sample),
                  "readings": {k: [v for v, _ in r]
                               for k, r in readings.items()},
                  "seconds": t_check, "compiles": counter.compiles["after"]},
    }), file=log)
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}", file=log)
    log.flush()
    out = {"correct": correct, "attempted": solves,
           "failed": len(failed_solves), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
