"""Pure-jnp oracles for tiled right-looking Cholesky factorization.

The paper's benchmark: 2Kx2K doubles in 128x128 tiles.  Tile ops:

* ``potrf``  — Cholesky of a diagonal tile
* ``trsm``   — panel solve  X L^T = A  (X strictly below the diagonal tile)
* ``update`` — trailing update  C - A @ B^T  (SYRK on the diagonal, GEMM off)

FLOPs are dominated by ``update`` (O(n^3/3) of the total), which is the
Pallas kernel (shared with :mod:`repro.kernels.matmul`); ``potrf``/``trsm``
on 128-wide tiles are left to XLA's native triangular ops — on TPU their
sequential dependency chains do not map onto the MXU, so the tiled
decomposition (exactly the paper's task structure) is what exposes the
hardware-friendly work.
"""
import jax
import jax.numpy as jnp


def potrf(a):
    """Lower-triangular Cholesky factor of a (tile-sized) SPD matrix."""
    return jnp.linalg.cholesky(a)


def trsm(l, a):
    """Solve ``x @ l.T = a`` for x (l lower-triangular)."""
    return jax.scipy.linalg.solve_triangular(l, a.T, lower=True).T


def update(c, a, b):
    """Trailing update ``c - a @ b.T`` (f32/f64 accumulation)."""
    acc = jnp.promote_types(c.dtype, jnp.float32)
    # HIGHEST: f32 accuracy on the TPU's MXU, as in kernels/matmul/ref.py
    # (XLA's own cholesky and triangular_solve already run at HIGHEST)
    prod = jnp.matmul(a, b.T, preferred_element_type=acc,
                      precision=jax.lax.Precision.HIGHEST)
    return (c.astype(acc) - prod).astype(c.dtype)


def cholesky_blocked(a, tile: int):
    """Reference tiled right-looking Cholesky (sequential loop nest) —
    the oracle for the task-graph version."""
    n = a.shape[0]
    g = n // tile
    t = {}
    for i in range(g):
        for j in range(i + 1):
            t[i, j] = a[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile]
    for k in range(g):
        t[k, k] = potrf(t[k, k])
        for i in range(k + 1, g):
            t[i, k] = trsm(t[k, k], t[i, k])
        for i in range(k + 1, g):
            for j in range(k + 1, i + 1):
                t[i, j] = update(t[i, j], t[i, k], t[j, k])
    out = jnp.zeros_like(a)
    for i in range(g):
        for j in range(i + 1):
            out = out.at[i * tile:(i + 1) * tile,
                         j * tile:(j + 1) * tile].set(t[i, j])
    return jnp.tril(out)
