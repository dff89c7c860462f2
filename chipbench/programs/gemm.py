"""Tiled ``C += A @ B`` as a ``@task`` program: PLASMA ``dgemm``'s task
graph and BDDT-SCC §4.2 MM.  Copied from ``benchmarks/apps.py`` so that
the workload stays fixed while the runtime under it changes.  The body
calls the program's tile kernel (``repro.kernels.matmul.ops``).
"""
from __future__ import annotations

from repro import task
from repro.kernels.matmul import ops as mm_ops


@task(inout="c", in_=("x", "y"))
def _gemm(c, x, y):
    return mm_ops.matmul(x, y, c)


def spawn(arrays: dict, grid: int) -> None:
    """Spawn one whole product into ``arrays["C"]``; the caller holds the
    runtime scope."""
    A, B, C = arrays["A"], arrays["B"], arrays["C"]
    for i in range(grid):
        for j in range(grid):
            for k in range(grid):
                _gemm(C[i, j], A[i, k], B[k, j])


OUTPUT = "C"
