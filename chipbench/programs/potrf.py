"""Tiled lower Cholesky as a ``@task`` program: PLASMA ``dpotrf``'s task
graph, as KASTORS runs it with OpenMP task dependences and as BDDT-SCC
§4.2 runs its Cholesky.  Copied from ``benchmarks/apps.py`` so that the
workload stays fixed while the runtime under it changes.  The bodies call
the program's tile kernels (``repro.kernels.cholesky.ops``).
"""
from __future__ import annotations

from repro import task
from repro.kernels.cholesky import ops as chol_ops


@task(inout="a")
def _potrf(a):
    return chol_ops.potrf(a)


@task(in_="l", inout="a")
def _trsm(l, a):
    return chol_ops.trsm(l, a)


@task(inout="c", in_=("x", "y"))
def _update(c, x, y):
    return chol_ops.update(c, x, y)


def spawn(arrays: dict, grid: int) -> None:
    """Spawn one whole factorization of ``arrays["A"]`` in place; the
    caller holds the runtime scope."""
    A = arrays["A"]
    for k in range(grid):
        _potrf(A[k, k])
        for i in range(k + 1, grid):
            _trsm(A[k, k], A[i, k])
        for i in range(k + 1, grid):
            for j in range(k + 1, i + 1):
                _update(A[i, j], A[i, k], A[j, k])


#: the array whose tiles hold the result
OUTPUT = "A"
