#!/usr/bin/env python3
"""Run the @task runtime's paper apps once on a TPU, in one process.

    python chip_smoke.py [--seed N]            # one chip
    python chip_smoke.py --chips 4 [--seed N]  # four chips: sharded only

With no option the script needs one TPU chip.  It drives the runtime's
main path through its normal entry points (``TaskRuntime``, ``@task`` and
``benchmarks.apps.run_app``) at the sizes in :data:`ONE_CHIP`: the five
paper apps on the ``staged`` executor with ``kernel_backend="xla"``, then
the apps again with ``kernel_backend="pallas"`` (fused wave kernels), then
cholesky on the ``sharded`` executor over a one-device mesh.  Every app
checks its result against its plain ``jax.numpy``/NumPy reference; each
phase runs twice (cold, compiling included, then warm).

``--chips 4`` runs only :func:`sharded_compare`: cholesky on the
``sharded`` executor with striped homes over a mesh of four devices, and
the same program staged on one of them, both checked against the
reference and against each other.

Every earlier output line is one JSON object: the size cuts, then one
line per phase.  The last line, printed only when every phase passed, is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

The script exits non-zero without that line when the platform is not
``tpu``, when Pallas would run in interpret mode, when a numerics check
fails, or when a pallas phase falls back on a group of the gemm or
trailing-update bodies (which the compiler accepts on 512^2 tiles).
The JAX compilation cache is on (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# the apps at full size: a 256 MiB cholesky matrix (816 tasks), a 4096-task
# matmul, a 64-tile Jacobi grid, the paper's 2-D FFT decomposition on
# 128-row blocks, and 2^21 Black-Scholes options in 32 tasks
SIZES = {
    "cholesky": {"n": 8192, "tile": 512},
    "matmul": {"n": 8192, "tile": 512},
    "jacobi": {"n": 8192, "tile": 512, "iters": 4},
    "fft": {"n": 2048, "row_block": 128, "tile": 128},
    "black_scholes": {"n_options": 2 ** 21, "task_options": 65536},
}

#: sizes cut from :data:`SIZES` because a run's time limit forced it
CUTS: list[str] = []

#: task bodies the TPU compiler accepts as fused wave kernels at these
#: sizes; a pallas phase may not fall back on one of their groups, except
#: for a one-task group (which never fuses)
FUSED_BODIES = frozenset({"_gemm", "_update"})


@dataclasses.dataclass(frozen=True)
class Phase:
    name: str
    app: str
    sizes: dict
    executor: str = "staged"
    kernel_backend: str = "xla"
    mesh: int = 0            # devices in the mesh (0 = no mesh)


ONE_CHIP = (
    *(Phase(app, app, SIZES[app]) for app in
      ("cholesky", "matmul", "jacobi", "fft", "black_scholes")),
    *(Phase(f"{app}/pallas", app, SIZES[app], kernel_backend="pallas")
      for app in ("matmul", "cholesky", "jacobi", "fft")),
    Phase("cholesky/sharded", "cholesky", SIZES["cholesky"],
          executor="sharded", mesh=1),
)


def _import_path() -> None:
    if not (ROOT / "src" / "repro").is_dir() or \
            not (ROOT / "benchmarks" / "apps.py").is_file():
        raise SystemExit("chip_smoke: run from a checkout of the repository "
                         "(src/repro and benchmarks/ must sit beside this "
                         "script)")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def device_info(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _mesh(devices, n: int):
    import numpy as np
    from jax.sharding import Mesh
    from repro import dist
    if not n:
        return contextlib.nullcontext()
    return dist.use_mesh(Mesh(np.asarray(devices[:n]), ("data",)))


def _timing_key(platform: str, what: str) -> str:
    # a wall time is labelled with the platform it was taken on, so a
    # host-CPU rehearsal never reads as a chip number
    return f"{platform}_wall_s_{what}"


def run_phase(phase: Phase, seed: int, devices) -> dict:
    """Run one phase twice (cold, then warm) and return its report.
    Raises when a numerics check fails or the phase breaks a rule."""
    from benchmarks.apps import run_app
    from repro.obs import InMemoryTracker

    info = device_info(devices)
    walls, stats, trk = [], None, None
    for _ in ("cold", "warm"):
        trk = InMemoryTracker() if phase.kernel_backend == "pallas" else None
        t0 = time.perf_counter()
        with _mesh(devices, phase.mesh):
            stats = run_app(phase.app, phase.executor,
                            app_kwargs={**phase.sizes, "seed": seed},
                            kernel_backend=phase.kernel_backend,
                            tracker=trk)
        walls.append(time.perf_counter() - t0)
    report = {
        "phase": phase.name, "app": phase.app, "executor": phase.executor,
        "kernel_backend": phase.kernel_backend, "mesh_devices": phase.mesh,
        "sizes": phase.sizes, "platform": info["platform"],
        "device_kind": info["kind"], "device_count": info["count"],
        _timing_key(info["platform"], "cold"): walls[0],
        _timing_key(info["platform"], "warm"): walls[1],
        # of the warm run's wall: host time spawning tasks (dependence
        # analysis included) and inside barriers (wave dispatch)
        _timing_key(info["platform"], "warm_spawn"): stats.spawn_time_s,
        _timing_key(info["platform"], "warm_barrier"): stats.barrier_time_s,
        "tasks": stats.tasks_spawned, "waves": stats.waves,
        # None under kernel_backend="xla", where the wave-kernel layer is
        # inert
        "kernel_dispatches": stats.kernel_dispatches,
        "kernel_fallbacks": stats.kernel_fallbacks,
        "kernel_fallback_reasons": stats.kernel_fallback_reasons,
        "numerics": "checked",
    }
    if phase.kernel_backend == "pallas":
        report.update(_pallas_report(phase, stats, trk))
    return report


def _pallas_report(phase: Phase, stats, trk) -> dict:
    """The (warm) run's compiler refusals by body, after its pallas rules:
    no fused body falls back, and matmul and cholesky fuse something."""
    refused: dict[str, str] = {}
    bad = []
    for e in trk.events_of("kernel_dispatch"):
        d = e.data
        if d["backend"] != "xla":
            continue
        if d.get("error"):
            refused.setdefault(d["fn"], d["error"])
        if d["fn"] in FUSED_BODIES and d["reason"] != "single_task":
            bad.append(f"{d['fn']} x{d['tasks']}: {d['reason']} "
                       f"{d.get('error', '')}".strip())
    if bad:
        raise AssertionError(f"{phase.name}: groups of the fused bodies "
                             f"fell back: {bad[:4]}")
    if phase.app in ("matmul", "cholesky") and not stats.kernel_dispatches:
        raise AssertionError(f"{phase.name}: no group fused")
    return {"compile_refused": refused}


def sharded_compare(sizes: dict, seed: int, devices, n_dev: int = 4) -> dict:
    """Cholesky on the ``sharded`` executor over ``n_dev`` devices with
    striped homes, against the same program staged on ``devices[0]``.
    Both runs check their result against the reference; the two results
    must agree, the tiles must spread evenly over every device, and no
    byte may be staged through an undeclared device."""
    import numpy as np
    from benchmarks.apps import APPS
    from repro import RuntimeConfig, TaskRuntime
    from repro.obs import InMemoryTracker

    if len(devices) < n_dev:
        raise AssertionError(f"need {n_dev} devices, found {len(devices)}")
    info = device_info(devices[:n_dev])

    def run(executor, mesh, tracker=None):
        with _mesh(devices, mesh):
            rt = TaskRuntime(RuntimeConfig(
                executor=executor, placement="striped", n_controllers=n_dev,
                tracker=tracker))
            try:
                t0 = time.perf_counter()
                A = APPS["cholesky"](rt, **sizes, seed=seed)
                wall = time.perf_counter() - t0
                return A, rt.stats(), wall
            finally:
                rt.shutdown()

    trk = InMemoryTracker()
    A_sh, s_sh, wall_sh = run("sharded", n_dev, trk)
    per_device: dict[str, int] = {}
    for idx in A_sh.block_indices():
        (dev,) = A_sh.get_tile(idx).devices()
        per_device[str(dev)] = per_device.get(str(dev), 0) + 1
    waves = trk.events_of("wave_close")
    run_traffic = {k: sum(e.data[k] for e in waves)
                   for k in ("tile_moves", "bytes_moved", "bytes_staged")}
    with_devices = {"tile_moves": s_sh.tile_moves,
                    "bytes_moved": s_sh.bytes_moved,
                    "bytes_staged": s_sh.bytes_staged}
    A_st, s_st, wall_st = run("staged", 0)
    got = np.tril(np.asarray(A_sh.gather(devices[0])))
    want = np.tril(np.asarray(A_st.gather()))
    # both runs do the same f32 task sequence and differ only in how
    # XLA batches it; a reassociated f32 dot of length `tile`, carried
    # through the g panel steps, bounds the drift at g * tile * 2^-24
    g = sizes["n"] // sizes["tile"]
    tol = g * sizes["tile"] * 2.0 ** -24
    max_abs = float(np.max(np.abs(got - want)))
    report = {
        "phase": f"cholesky/sharded{n_dev}", "sizes": sizes,
        "platform": info["platform"], "device_kind": info["kind"],
        "device_count": info["count"],
        _timing_key(info["platform"], "sharded"): wall_sh,
        _timing_key(info["platform"], "staged1"): wall_st,
        "tasks": s_sh.tasks_spawned, "waves": s_sh.waves,
        "sharded_dispatches": s_sh.sharded_dispatches,
        "tiles_per_device": per_device,
        "waves_traffic": run_traffic,
        "traffic_incl_readback": with_devices,
        "max_abs_diff_vs_staged": max_abs, "diff_tol": tol,
        "numerics": "checked",
    }
    errors = []
    if len(per_device) != n_dev:
        errors.append(f"tiles sit on {len(per_device)} of {n_dev} devices")
    elif max(per_device.values()) - min(per_device.values()) > 1:
        errors.append(f"tiles unbalanced under striping: {per_device}")
    if s_sh.bytes_staged:
        errors.append(f"bytes_staged={s_sh.bytes_staged}")
    try:
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    except AssertionError as e:
        errors.append(f"sharded != staged: {str(e).strip()[:400]}")
    if errors:
        raise AssertionError("; ".join(errors) + f" | {json.dumps(report)}")
    return report


def _refuse(devices) -> str | None:
    """Why this process may not report a chip run (None = it may)."""
    from repro.core import wavekernel
    if devices[0].platform != "tpu":
        return f"platform is {devices[0].platform!r}, not 'tpu'"
    if "REPRO_PALLAS_INTERPRET" in os.environ:
        return "REPRO_PALLAS_INTERPRET is set"
    if wavekernel.interpret_mode():
        return "Pallas would run in interpret mode"
    return None


def _emit(obj) -> None:
    print(json.dumps(obj, sort_keys=True, default=str), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every app's input data")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded comparison")
    args = ap.parse_args(argv)

    _import_path()
    import jax

    devices = jax.devices()
    why = _refuse(devices)
    if why is not None:
        print(f"chip_smoke: {why}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    _emit({"compile_cache": enable_compile_cache(), "cuts": CUTS,
           "device": device_info(devices[:args.chips])})
    failed = []
    if args.chips == 4:
        jobs = [("cholesky/sharded4",
                 lambda: sharded_compare(SIZES["cholesky"], args.seed,
                                         devices))]
    else:
        jobs = [(p.name, lambda p=p: run_phase(p, args.seed, devices))
                for p in ONE_CHIP]
    for name, job in jobs:
        try:
            _emit(job())
        except Exception as e:              # report, run the next phase
            traceback.print_exc()
            failed.append(name)
            _emit({"phase": name, "failed": f"{type(e).__name__}: "
                                            f"{str(e).strip()[:2000]}"})
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    # the contract line, keys in this order and nothing more
    print(json.dumps({"ok": True,
                      "device": device_info(devices[:args.chips])}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
