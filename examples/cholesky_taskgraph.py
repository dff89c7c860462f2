"""Task-parallel tiled Cholesky — the paper's hardest benchmark.

The right-looking factorization's three kernels are declared once as
``@task`` functions; calling them inside the runtime scope spawns tile
tasks whose footprints overlap heavily.  BDDT dependence analysis
discovers the DAG (RAW through the panel, WAW on diagonal updates) and
the staged executor runs it in wavefronts.  The update tasks run XLA's
dot; only under ``RuntimeConfig(kernel_backend="pallas")`` on a TPU does a
wave of them become one fused Pallas kernel (``core/wavekernel.py``).
``wait_on(A[0, 0])`` demonstrates
region-scoped sync: the first diagonal tile is final long before the
trailing submatrix drains.

    PYTHONPATH=src python examples/cholesky_taskgraph.py
"""
import numpy as np
import jax.numpy as jnp

from repro import TaskRuntime, task
from repro.kernels.cholesky import ops as chol


@task(inout="a")
def potrf(a):
    return chol.potrf(a)


@task(in_="l", inout="a")
def trsm(l, a):
    return chol.trsm(l, a)


@task(inout="c", in_=("a", "b"))
def update(c, a, b):
    return chol.update(c, a, b)


def main(n: int = 512, tile: int = 64):
    g = n // tile
    rng = np.random.default_rng(0)
    m = rng.standard_normal((n, n)).astype(np.float32)
    spd = m @ m.T + n * np.eye(n, dtype=np.float32)

    with TaskRuntime(executor="staged", placement="striped_diag") as rt:
        A = rt.from_array(spd, (tile, tile), name="A")

        for k in range(g):
            potrf(A[k, k])
            for i in range(k + 1, g):
                trsm(A[k, k], A[i, k])
            for i in range(k + 1, g):
                for j in range(k + 1, i + 1):
                    update(A[i, j], A[i, k], A[j, k])

        # the top-left tile's cone is just potrf(A[0,0]): ready immediately
        rt.wait_on(A[0, 0])
        top = np.asarray(A[0, 0].materialize())
        np.testing.assert_allclose(
            np.tril(top), np.asarray(jnp.linalg.cholesky(
                jnp.asarray(spd[:tile, :tile]))), rtol=2e-2, atol=2e-2)

        rt.barrier()
        got = np.tril(np.asarray(A.gather()))
        want = np.asarray(jnp.linalg.cholesky(jnp.asarray(spd)))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
        s = rt.stats()
        print(f"cholesky {n}x{n}/{tile}: {s.tasks_spawned} tasks, "
              f"{s.deps_found} deps, {s.waves} wavefronts "
              f"-> factor verified against jnp.linalg.cholesky")
        print("wavefront width = available parallelism per step; the "
              "paper's 22-worker saturation is this DAG's critical path "
              "showing up")


if __name__ == "__main__":
    main()
