"""Unified benchmark harness — one CLI over the microbenchmarks, the DES
paper suite, the granularity sweep, and the real ``@task`` applications.

Runs the cost-model calibration first (``repro.core.calibrate``), then
every sweep on the *calibrated* parameters, then the five paper apps for
real on the staged / sharded / sim executors — the sim runs use the
default flopcount-derived cost, so the JSON records both measured wall
time and the DES's predicted SCC time for the same task program.

    PYTHONPATH=src python -m benchmarks.run --suite smoke --emit BENCH_4.json
    PYTHONPATH=src python -m benchmarks.run --suite paper

Output: ``name,metric,value`` CSV lines for humans, a validation summary
against the paper's claims (exit 1 on failure), and — with ``--emit`` — a
machine-readable BENCH JSON document (schema ``bddt-scc-bench/1``,
specified in docs/BENCHMARKS.md) that ``tools/bench_gate.py`` diffs
against the committed baseline in CI and ``benchmarks.report`` renders
as a table.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time

SCHEMA = "bddt-scc-bench/1"
# the wall-time trend block: informational only, validated for shape by
# tools/bench_gate.py but never regression-gated (wall times are noisy on
# shared CI runners; the nightly series exists to eyeball trends)
TIMINGS_SCHEMA = "bddt-scc-timings/1"

# problem sizes per suite: "smoke" shrinks both the synthetic DES
# workloads and the real app instances so the whole suite fits in a CI
# job; "paper" is the §4.2 configuration
SUITES: dict = {
    "smoke": {
        "worker_counts": [1, 4, 8, 16, 43],
        "workload_sizes": {
            "black_scholes": {"n_options": 200_000},
            "matmul": {"n": 512},
            "fft": {"n": 512},
            "jacobi": {"n": 2048, "iters": 4},
            "cholesky": {"n": 1024},
        },
        "granularity": {"n": 512, "tiles": (128, 64, 32, 16)},
        "app_sizes": {
            "black_scholes": {"n_options": 2048, "task_options": 256},
            "matmul": {"n": 128, "tile": 32},
            "fft": {"n": 64, "row_block": 16, "tile": 16},
            "jacobi": {"n": 128, "tile": 32, "iters": 2},
            "cholesky": {"n": 128, "tile": 32},
        },
        "app_workers": 8,
        "paper_ranges": False,
        "owner_skew": 0.0,              # override off in the CI profile
    },
    "paper": {
        "worker_counts": None,          # paper_suite.WORKER_COUNTS
        "workload_sizes": {},
        "granularity": {"n": 1024, "tiles": (256, 128, 64, 32, 16)},
        "app_sizes": {},                # apps.py defaults
        "app_workers": 8,
        "paper_ranges": True,
        # the paper suite reports striped vs striped+override: spill when
        # one home owns > 1.5x the mean wave load
        "owner_skew": 1.5,
    },
}


def _report(name: str, metric, value) -> None:
    print(f"{name},{metric},{value}")


def runtime_overheads(report) -> dict:
    """Master-side costs of the real (host) runtime: spawn + dependence
    analysis latency — the quantity the paper's master-bottleneck finding
    hinges on."""
    from repro import TaskRuntime, task

    @task(inout="x")
    def tick(x):
        return x * 1.0

    with TaskRuntime(executor="staged") as rt:
        A = rt.zeros((64, 64), (8, 8))
        # warm up
        tick(A[0, 0])
        rt.barrier()
        n = 2000
        t0 = time.perf_counter()
        for i in range(n):
            tick(A[i % 8, (i // 8) % 8])
        dt = time.perf_counter() - t0
        rt.barrier()
        spawn_us = dt / n * 1e6
        report("runtime_overhead", "spawn_us_per_task", round(spawn_us, 2))
        s = rt.stats()
        blocks_per_task = s.blocks_walked / max(s.tasks_spawned, 1)
        report("runtime_overhead", "blocks_walked_per_task", blocks_per_task)
    return {"spawn_us": spawn_us, "blocks_walked_per_task": blocks_per_task}


def _bench_mesh():
    """A mesh over *every* local device (identical to
    ``dist.single_device_mesh()`` when there is one).  The CI bench jobs
    force 2 host devices via ``XLA_FLAGS``, so the sharded app runs
    measure real cross-device residency — ``tile_moves`` counts actual
    transfers and the ``no_operand_staging`` check can genuinely fail if
    a dispatch path ever stages operands again."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()), ("data",))


def app_entries(cfg: dict, report, sim_params=None,
                owner_skew: float = 0.0, tracker=None,
                profile_waves: bool = False) -> list[dict]:
    """The five paper apps as real task programs: staged (wall time +
    dispatch counts), sharded on a mesh over all local devices
    (deterministic cross-home traffic of the striped placement plus the
    measured residency counters — ``bytes_staged`` must stay 0), and sim
    twice — striped and single placement — predicting SCC time on
    ``sim_params`` (the calibrated model when called from
    :func:`build_bench`).  With ``owner_skew > 0`` each app runs sharded
    once more with the contention-aware owner override, so the artifact
    reports striped vs striped+override side by side."""
    from repro import dist
    from .apps import APPS, run_app

    entries = []
    workers = cfg["app_workers"]
    trk = {} if tracker is None else {"tracker": tracker}
    for name in sorted(APPS):
        kw = cfg["app_sizes"].get(name, {})
        t0 = time.perf_counter()
        staged = run_app(name, "staged", app_kwargs=kw, n_workers=workers,
                         profile_waves=profile_waves, **trk)
        wall_staged = time.perf_counter() - t0
        with dist.use_mesh(_bench_mesh()):
            sharded = run_app(name, "sharded", app_kwargs=kw,
                              n_workers=workers,
                              profile_waves=profile_waves, **trk)
        sim = run_app(name, "sim", app_kwargs=kw, n_workers=workers,
                      sim_params=sim_params)
        sim1 = run_app(name, "sim", app_kwargs=kw, n_workers=workers,
                       placement="single", sim_params=sim_params)
        report(f"app_{name}", "wall_s_staged", round(wall_staged, 3))
        report(f"app_{name}", "sim_predicted_s", sim.predicted_total_s)
        report(f"app_{name}", "cross_home_MiB",
               round(sharded.cross_home_bytes / 2**20, 3))
        report(f"app_{name}", "bytes_staged", sharded.bytes_staged)
        metrics = {
            "tasks": staged.tasks_spawned,
            "deps": staged.deps_found,
            "waves": staged.waves,
            "grouped_dispatches": staged.grouped_dispatches,
            "cross_home_bytes": sharded.cross_home_bytes,
            "local_home_bytes": sharded.local_home_bytes,
            # residency: measured at the memory layer.  bytes_staged is
            # gated at zero (any staging hop regresses); tile_moves are
            # the actual transfers on the bench mesh (CI forces 2 host
            # devices, so these are real cross-device moves) and the
            # sim's predicted cross-home fetches carry the footprint view
            "bytes_staged": sharded.bytes_staged,
            "tile_moves": sharded.tile_moves,
            "sim_tile_moves": sim.tile_moves,
            "sim_predicted_s": sim.predicted_total_s,
            "sim_predicted_single_mc_s": sim1.predicted_total_s,
        }
        info = {
            "sizes": kw,
            "n_workers": workers,
            "wall_s_staged": wall_staged,
            "spawn_us_per_task": staged.spawn_us_per_task,
        }
        if owner_skew > 0:
            with dist.use_mesh(_bench_mesh()):
                skewed = run_app(name, "sharded", app_kwargs=kw,
                                 n_workers=workers,
                                 owner_skew_threshold=owner_skew)
            report(f"app_{name}", "owner_overrides", skewed.owner_overrides)
            info["owner_skew_threshold"] = owner_skew
            metrics["owner_overrides"] = skewed.owner_overrides
            metrics["cross_home_bytes_skew"] = skewed.cross_home_bytes
        entries.append({
            "id": f"app/{name}",
            "kind": "app",
            "info": info,
            "metrics": metrics,
        })
    return entries


# apps in the kernel-backend sweep: every wave is rectangular and
# homogeneous, so under the Pallas interpreter (the CPU runs this suite
# takes) the pallas backend must fuse them all — a fallback here means the
# eligibility rules or the grouping signature regressed.  On a TPU the
# compiler refuses Jacobi's stencil, a fallback named "compile_refused"
KERNEL_SWEEP_APPS = ("matmul", "jacobi")


def kernel_backend_entries(cfg: dict, report) -> list[dict]:
    """The staged executor's two dispatch backends side by side: XLA
    vmap/jit vs the fused pallas wave kernels (one ``pallas_call`` grid
    per wave group).  Wall clocks are informational only — on CPU CI the
    pallas path runs in interpret mode, which is a correctness harness,
    not a perf claim.  The *gated* metrics are the deterministic
    dispatch/fallback counts; both runs self-verify numerics inside
    ``run_app``."""
    from .apps import run_app

    entries = []
    workers = cfg["app_workers"]
    for name in KERNEL_SWEEP_APPS:
        kw = cfg["app_sizes"].get(name, {})
        t0 = time.perf_counter()
        xla = run_app(name, "staged", app_kwargs=kw, n_workers=workers)
        wall_xla = time.perf_counter() - t0
        t0 = time.perf_counter()
        pal = run_app(name, "staged", app_kwargs=kw, n_workers=workers,
                      kernel_backend="pallas")
        wall_pal = time.perf_counter() - t0
        report(f"kernel_backend_{name}", "wall_s_xla", round(wall_xla, 3))
        report(f"kernel_backend_{name}", "wall_s_pallas",
               round(wall_pal, 3))
        report(f"kernel_backend_{name}", "kernel_dispatches",
               pal.kernel_dispatches)
        report(f"kernel_backend_{name}", "kernel_fallbacks",
               pal.kernel_fallbacks)
        entries.append({
            "id": f"kernel_backend/{name}",
            "kind": "kernel_backend",
            "info": {"sizes": kw, "n_workers": workers,
                     "wall_s_xla": wall_xla, "wall_s_pallas": wall_pal},
            "metrics": {
                "kernel_dispatches": pal.kernel_dispatches,
                "kernel_fallbacks": pal.kernel_fallbacks,
                "waves": pal.waves,
                "grouped_dispatches": xla.grouped_dispatches,
            },
        })
    return entries


def build_bench(suite: str, *, skip_roofline: bool = True,
                report=_report,
                owner_skew: float | None = None,
                trace: str | None = None,
                profile_dir: str | None = None) -> tuple[dict, bool]:
    """Run the whole suite; returns (BENCH document, all checks passed).
    ``owner_skew`` overrides the suite's owner-override threshold (None =
    the suite default: off for smoke, 1.5 for paper).  ``trace`` writes a
    JSONL wave trace of the staged and sharded app runs there (the CI
    artifact; open it with ``python -m repro.obs summary`` or export to
    Chrome via ``python -m repro.obs chrome``).  ``profile_dir`` brackets
    the app runs in a ``jax.profiler`` trace session writing there, with
    ``profile_waves`` wave annotations enabled, so the per-wave spans
    land in the uploaded trace files (no-op if jax lacks the API)."""
    import dataclasses

    from repro.core.calibrate import calibrate, validate_trends
    from repro.obs import profile_session
    from . import granularity, microbench, paper_suite

    cfg = SUITES[suite]
    if owner_skew is None:
        owner_skew = cfg["owner_skew"]
    t_start = time.perf_counter()

    # 1. calibration: fit SCCParams to the paper's Fig 3/4 anchors and
    # check the fitted model still shows the paper's trends — validated
    # explicitly (not via calibrate()'s raise) so a broken trend lands in
    # the validation summary as a FAIL line instead of a traceback
    cal = calibrate(validate=False)
    cal = dataclasses.replace(cal, checks=validate_trends(cal.params))
    p = cal.params
    for k, v in cal.as_dict().items():
        if k != "checks":
            report("calibration", k, v)

    # 2. model microbenchmarks + DES sweeps, all on calibrated params
    micro = microbench.run(report, p)
    sweeps = paper_suite.run(report, p=p,
                             worker_counts=cfg["worker_counts"],
                             sizes=cfg["workload_sizes"])
    gran = granularity.run(report, p=p, **cfg["granularity"])

    # 3. the real @task programs (sim runs predict on the fitted model)
    tracker = None
    if trace:
        from repro.obs import JsonlTracker
        tracker = JsonlTracker(trace)
    try:
        with profile_session(profile_dir) as profiling:
            if profile_dir:
                report("profile", "session", "on" if profiling else
                       "unavailable")
            apps = app_entries(cfg, report, sim_params=p,
                               owner_skew=owner_skew, tracker=tracker,
                               profile_waves=profiling)
    finally:
        if tracker is not None:
            tracker.close()
            report("trace", "events", tracker.records_written)
    kb = kernel_backend_entries(cfg, report)
    over = runtime_overheads(report)

    # 4. master-side admission throughput: central analyzer vs the
    # home-sharded dependence managers on a streaming synthetic graph
    # (deterministic counters gated; measured rates info-only)
    from .spawn_throughput import entry as spawn_throughput_entry
    spawn = spawn_throughput_entry(suite)
    for k, v in spawn["info"].items():
        if isinstance(v, float):
            report("spawn_throughput", k, round(v, 2))

    # 5. streaming serving: deterministic admission counters (gated) +
    # the open-loop latency sweep (info-only wall clocks)
    from .serving import entry as serving_entry
    serving = serving_entry(suite)
    for k in ("submitted", "admitted", "rejected"):
        report("serving", k, int(serving["metrics"][k]))
    for rate, r in serving["info"]["rates"].items():
        report("serving", f"p99_ms_at_{rate}rps", round(r["p99_ms"], 2))
        report("serving", f"throughput_at_{rate}rps",
               round(r["throughput_rps"], 1))

    entries: list[dict] = [{
        "id": "microbench",
        "kind": "microbench",
        "info": {},
        "metrics": {"fig3_far_vs_near": micro["fig3_far_near"],
                    "fig4_32_vs_1": micro["fig4_32_1"]},
    }]
    for name, s in sweeps.items():
        metrics = {f"speedup_w{r['workers']}": r["speedup"]
                   for r in s["rows"]}
        metrics["peak_speedup"] = s["peak_speedup"]
        metrics["speedup_single_mc"] = s["speedup_43_single_mc"]
        metrics["busy_cv"] = s["busy_cv_43"]
        entries.append({
            "id": f"scalability/{name}",
            "kind": "scalability",
            "checkpoints": [{k: r[k] for k in
                             ("workers", "time_s", "speedup")}
                            for r in s["rows"]],
            "info": {"peak_workers": s["peak_workers"]},
            "metrics": metrics,
        })
    best = max(range(len(gran)), key=lambda i: gran[i]["speedup"])
    entries.append({
        "id": "granularity",
        "kind": "granularity",
        "rows": gran,
        "info": {"best_tile": gran[best]["tile"]},
        "metrics": {**{f"speedup_tile{r['tile']}": r["speedup"]
                       for r in gran},
                    "peak_speedup": gran[best]["speedup"]},
    })
    entries.extend(apps)
    entries.extend(kb)
    entries.append({
        "id": "runtime_overhead",
        "kind": "overhead",
        "info": {"spawn_us_per_task": over["spawn_us"]},
        "metrics": {
            "blocks_walked_per_task": over["blocks_walked_per_task"]},
    })
    entries.append(spawn)
    entries.append(serving)

    roofline_note = "skipped (--skip-roofline)"
    if not skip_roofline:
        try:
            from . import roofline
            roofline.run(report)
            roofline_note = "ok"
        except Exception as e:  # dry-run artifacts missing
            roofline_note = str(e)[:80]
            report("roofline", "skipped", roofline_note)

    # ---- validation vs the paper's claims -------------------------------
    by_id = {e["id"]: e for e in entries}
    gemm_sim = by_id["app/matmul"]["metrics"]
    checks = {
        # calibration reproduced the microbenchmark shapes and trends
        "calibration_ok": cal.ok and cal.fig3_max_rel_err < 0.05
        and cal.fig4_max_rel_err < 0.05,
        # Fig 3/4 shapes on the fitted model
        "fig3_latency_grows_with_hops": micro["fig3_far_near"] > 1.2,
        "fig4_contention_grows": micro["fig4_32_1"] > 5.0,
        # striping beats single-controller placement for the memory-bound
        # apps (the paper's placement fix) — on the DES workloads
        "striping_helps_fft":
            sweeps["fft"]["speedup_43_single_mc"]
            < 0.7 * sweeps["fft"]["speedup_43"],
        "striping_helps_jacobi":
            sweeps["jacobi"]["speedup_43_single_mc"]
            < 0.7 * sweeps["jacobi"]["speedup_43"],
        # ... and on the *real* gemm task program under executor="sim"
        # with the default flopcount-derived cost
        "sim_app_striped_beats_single":
            gemm_sim["sim_predicted_s"]
            < gemm_sim["sim_predicted_single_mc_s"],
        # granularity: the optimum is interior (too fine hits the master
        # bottleneck, too coarse starves workers)
        "granularity_interior_optimum": 0 < best < len(gran) - 1,
        # residency: no app's sharded wave dispatches staged operand
        # bytes through a non-home device (the ISSUE 5 acceptance bar)
        "no_operand_staging": all(
            e["metrics"]["bytes_staged"] == 0
            for e in entries if e["kind"] == "app"),
        # the pallas wave-kernel backend fuses every wave of the
        # rectangular apps (no silent degradation to the XLA fallback)
        "pallas_backend_fuses": all(
            e["metrics"]["kernel_fallbacks"] == 0
            and e["metrics"]["kernel_dispatches"] > 0
            for e in kb),
        # descriptor-line batching really packs the wire (envelopes <
        # logical messages) and the DES replay of the logical stream
        # predicts the measured envelope/line counts for both pump modes
        "dep_batching_packs":
            spawn["metrics"]["dep_batches_8_homes_threaded"]
            < spawn["metrics"]["dep_messages_8_homes"],
        "dep_traffic_reconciled":
            spawn["metrics"]["traffic_reconciled"] == 1.0,
        # serving admission is a closed ledger — every submitted request
        # resolved exactly one way, and the controller provably kept the
        # in-flight footprint inside the byte budget
        "serving_admission_consistent":
            serving["metrics"]["admitted"] + serving["metrics"]["rejected"]
            == serving["metrics"]["submitted"]
            and serving["metrics"]["peak_in_flight_bytes"]
            <= serving["metrics"]["budget_bytes"],
    }
    if cfg["paper_ranges"]:
        checks.update({
            # Fig 5: MM scales to ~33x (we accept 25-40)
            "mm_speedup_43_in_range":
                25 <= sweeps["matmul"]["speedup_43"] <= 40,
            # BS scales near-linearly but sub-ideal (paper ~16x)
            "bs_speedup_43_in_range":
                10 <= sweeps["black_scholes"]["speedup_43"] <= 25,
            # FFT saturates around 16 workers
            "fft_saturates": sweeps["fft"]["peak_speedup"] < 8,
            # load stays balanced for BS/MM (Fig 7)
            "bs_balanced": sweeps["black_scholes"]["busy_cv_43"] < 0.2,
            "mm_balanced": sweeps["matmul"]["busy_cv_43"] < 0.2,
            # finest tiles lose to mid tiles (master bottleneck)
            "granularity_master_bottleneck":
                gran[-1]["speedup"] < gran[-3]["speedup"],
        })
    ok = sum(bool(v) for v in checks.values())
    for k, v in checks.items():
        report("validation", k, "PASS" if v else "FAIL")
    report("validation", "total", f"{ok}/{len(checks)}")
    wall = time.perf_counter() - t_start
    report("harness", "wall_s", round(wall, 1))

    try:
        import jax
        jax_version = jax.__version__
    except Exception:
        jax_version = None
    doc = {
        "schema": SCHEMA,
        "suite": suite,
        "wall_s": wall,
        "env": {"python": platform.python_version(), "jax": jax_version},
        "calibration": cal.as_dict(),
        "entries": entries,
        # informational wall-time trends (TIMINGS_SCHEMA): shape-validated
        # by bench_gate but never diffed against a baseline
        "timings": {
            "schema": TIMINGS_SCHEMA,
            "suite": suite,
            "suite_wall_s": wall,
            "staged_wall_s": {
                e["id"].split("/", 1)[1]: e["info"]["wall_s_staged"]
                for e in entries if e["kind"] == "app"},
            "spawn_us_per_task": over["spawn_us"],
        },
        "validation": {"checks": {k: bool(v) for k, v in checks.items()},
                       "passed": ok, "total": len(checks),
                       "roofline": roofline_note},
    }
    return doc, ok == len(checks)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="BDDT-SCC benchmark suite (schema: " + SCHEMA + ")")
    ap.add_argument("--suite", choices=sorted(SUITES), default="smoke",
                    help="problem-size profile (smoke=CI, paper=§4.2)")
    ap.add_argument("--emit", metavar="PATH",
                    help="write the BENCH JSON document here")
    ap.add_argument("--skip-roofline", action="store_true",
                    help="skip reading dry-run artifacts")
    ap.add_argument("--owner-skew", type=float, default=None,
                    metavar="THRESHOLD",
                    help="contention-aware owner override threshold for "
                         "the sharded app runs (adds striped+override "
                         "metrics; default: suite setting — off for "
                         "smoke, 1.5 for paper)")
    ap.add_argument("--trace", metavar="PATH",
                    help="write a JSONL wave trace of the staged/sharded "
                         "app runs (repro.obs event schema)")
    ap.add_argument("--profile-dir", metavar="DIR",
                    help="bracket the app runs in a jax.profiler trace "
                         "session writing here, with per-wave "
                         "profile_waves annotations enabled")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,metric,value")
    doc, ok = build_bench(args.suite, skip_roofline=args.skip_roofline,
                          owner_skew=args.owner_skew, trace=args.trace,
                          profile_dir=args.profile_dir)
    if args.emit:
        with open(args.emit, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.emit} ({len(doc['entries'])} entries)")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
