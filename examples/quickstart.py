"""Quickstart: the BDDT-SCC programming model in five minutes.

Declare each kernel's footprint once with ``@task`` (OmpSs's pragma as a
decorator), then call it naturally inside a runtime scope — every call
spawns a task, the runtime discovers dependencies block-by-block, and
synchronization is exactly as fine-grained as you ask for:

* ``future.result()``    — force one task's dependence cone;
* ``rt.wait_on(region)`` — taskwait scoped to a footprint;
* ``rt.barrier()``       — global drain (implied at scope exit).

Scalar parameters go in ``firstprivate``: they are bound at the call
site like everything else, but passed *by value* in the task descriptor
(OmpSs firstprivate) instead of synchronized on — and on the staged
executor, tasks that differ only in those values still share one batched
vmap dispatch.

Swap ``executor=`` between the paper-faithful dynamic host runtime, the
TPU-idiomatic staged wavefront executor, and the home-aware sharded
executor — results are identical (serial elision).  Outside a runtime
scope the decorated function runs eagerly, so ``gemm_tile(c, a, b)`` is
its own reference implementation.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro import dist
from repro import RuntimeConfig, TaskRuntime, task


@task(inout="c", in_=("a", "b"))
def gemm_tile(c, a, b):
    """One tile task: C[i,j] += A[i,k] @ B[k,j]."""
    return c + a @ b


@task(in_="x", out="y", firstprivate="shift")
def roll_tile(x, shift, y=None):
    """An index-parameterized task: ``shift`` is firstprivate — a plain
    value riding in the task descriptor, different for every spawn."""
    return jnp.roll(x, shift, axis=0)


def main():
    n, tile = 512, 64
    g = n // tile
    rng = np.random.default_rng(0)
    a = rng.standard_normal((n, n), dtype=np.float32)
    b = rng.standard_normal((n, n), dtype=np.float32)

    for executor in ("host", "staged"):
        cfg = RuntimeConfig(executor=executor, n_workers=4, mpb_slots=8,
                            policy="locality")
        with TaskRuntime(cfg) as rt:
            A = rt.from_array(a, (tile, tile), name="A")
            B = rt.from_array(b, (tile, tile), name="B")
            C = rt.zeros((n, n), (tile, tile), name="C")

            # OmpSs-style task loop: footprints give the runtime everything
            # it needs — no locks, no barriers between dependent tasks
            futures = {}
            for i in range(g):
                for j in range(g):
                    for k in range(g):
                        futures[i, j, k] = gemm_tile(C[i, j], A[i, k],
                                                     B[k, j])

            # force one output tile: runs only its g-task dependence chain
            tile00 = futures[0, 0, g - 1].result()
            np.testing.assert_allclose(np.asarray(tile00),
                                       a[:tile] @ b[:, :tile],
                                       rtol=2e-4, atol=2e-4)

            # region-scoped taskwait: top block row is done after this,
            # unrelated tiles may still be in flight
            rt.wait_on(C[0, 0:g])

            rt.barrier()
            got = np.asarray(C.gather())
            np.testing.assert_allclose(got, a @ b, rtol=2e-4, atol=2e-4)

            s = rt.stats()
            print(f"[{executor:6s}] {s.tasks_spawned} tasks, "
                  f"{s.deps_found} dependencies, "
                  f"{s.spawn_us_per_task:.1f} us/spawn, "
                  f"{s.futures_resolved} futures, "
                  f"{s.region_waits} region waits -> result verified")

    # firstprivate values: one function, per-task shift amounts — the
    # staged executor batches all g tasks into a single vmap dispatch.
    # tracker="console" turns on the observability layer (repro.obs):
    # every wave emits open/close events with dispatch wall time and
    # measured tile movement, summarized on stdout at shutdown — swap in
    # "jsonl:trace.jsonl" to capture the full timeline instead (then
    # `python -m repro.obs chrome trace.jsonl -o trace.json` renders it
    # for chrome://tracing or https://ui.perfetto.dev)
    with TaskRuntime(executor="staged", tracker="console") as rt:
        X = rt.from_array(a, (tile, n), name="X")
        Y = rt.zeros((n, n), (tile, n), name="Y")
        for r in range(g):
            roll_tile(X[r, 0], r + 1, Y[r, 0])
        rt.barrier()
        got = np.asarray(Y.gather())
        for r in range(g):
            np.testing.assert_array_equal(
                got[r * tile:(r + 1) * tile],
                np.roll(a[r * tile:(r + 1) * tile], r + 1, axis=0))
        s = rt.stats()
        print(f"[staged] firstprivate: {s.tasks_spawned} index-"
              f"parameterized tasks -> {s.grouped_dispatches} batched "
              f"dispatch(es) across {s.waves} wave(s)")

    # home-aware mesh execution: blocks keep the homes the placement
    # policy assigned (the paper's controller striping), the sharded
    # executor runs each task on the home device of its output block
    # (owner-computes) and reports the cross-home read traffic that
    # placement decision implies — the quantity the paper's §4 findings
    # hinge on.  Here the mesh is the one-device fallback, so the same
    # code path CI runs is exactly what a real mesh would execute.
    mesh = dist.single_device_mesh()
    n_dev = int(np.asarray(mesh.devices).size)
    with dist.use_mesh(mesh):
        with TaskRuntime(executor="sharded", placement="striped") as rt:
            A = rt.from_array(a, (tile, tile), name="A")
            B = rt.from_array(b, (tile, tile), name="B")
            C = rt.zeros((n, n), (tile, tile), name="C")
            for i in range(g):
                for j in range(g):
                    for k in range(g):
                        gemm_tile(C[i, j], A[i, k], B[k, j])
            rt.barrier()
            np.testing.assert_allclose(np.asarray(C.gather()), a @ b,
                                       rtol=2e-4, atol=2e-4)
            s = rt.stats()
            total = s.cross_home_bytes + s.local_home_bytes
            print(f"[sharded] owner-computes on a {n_dev}-device mesh: "
                  f"{s.sharded_dispatches} per-owner group programs, "
                  f"{s.cross_home_bytes / 2**20:.1f} MiB cross-home of "
                  f"{total / 2**20:.1f} MiB touched "
                  f"({100 * s.cross_home_bytes / total:.0f}% remote) "
                  f"-> result verified")


if __name__ == "__main__":
    main()
