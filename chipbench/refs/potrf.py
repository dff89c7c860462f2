"""Inputs, plain reference, kernel costs and check of tiled Cholesky.

Nothing here imports the system under test.  The reference is a blocked
right-looking Cholesky in plain ``jax.numpy``: XLA's own factor and
triangular solve on ``ref_block``-wide diagonal blocks and panels, and the
trailing update as one dot through ``dot``.  Its block is not the
program's tile, so the two agree only as far as f32 rounding lets them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.refs import dots

#: names of the program's input arrays, in the order :func:`make_inputs`
#: returns them
INPUTS = ("A",)


def make_inputs(key, cfg: dict) -> dict:
    """A symmetric positive definite ``n x n`` matrix made on the device
    from ``key``: ``M M^T / n + I`` with ``M`` standard normal, so its
    eigenvalues lie in about ``[1, 5]``."""
    n = cfg["n"]

    @jax.jit
    def gen(key):
        m = jax.random.normal(key, (n, n), jnp.float32)
        a = jnp.matmul(m, m.T, precision=jax.lax.Precision.HIGHEST) / n
        a = 0.5 * (a + a.T)
        return a + jnp.eye(n, dtype=jnp.float32)

    return {"A": gen(key)}


@functools.partial(jax.jit, static_argnames=("block", "dot"))
def cholesky(a, *, block: int, dot=dots.highest):
    """Lower Cholesky factor of ``a``, blocked right-looking."""
    n = a.shape[0]
    out = jnp.zeros_like(a)
    for k0 in range(0, n, block):
        k1 = min(k0 + block, n)
        d = jnp.linalg.cholesky(a[k0:k1, k0:k1])
        out = out.at[k0:k1, k0:k1].set(d)
        if k1 == n:
            break
        panel = jax.scipy.linalg.solve_triangular(
            d, a[k1:, k0:k1].T, lower=True).T
        out = out.at[k1:, k0:k1].set(panel)
        a = a.at[k1:, k1:].add(-dot(panel, panel.T))
    return out


def reference(inputs: dict, cfg: dict, dot=dots.highest):
    return cholesky(inputs["A"], block=cfg["ref_block"], dot=dot)


def output_indices(grid: int) -> list[tuple[int, int]]:
    """The tiles the factorization writes: the lower triangle."""
    return [(i, j) for i in range(grid) for j in range(i + 1)]


@jax.jit
def _gaps(got, want):
    d = jnp.tril(got) - want
    return (jnp.max(jnp.abs(d)) / jnp.max(jnp.abs(want)),
            jnp.linalg.norm(d) / jnp.linalg.norm(want))


def compare(tiles: dict, want, tile: int) -> dict:
    """The check's numbers for one solve's output ``tiles`` (index ->
    array) against the reference factor ``want``, over the lower
    triangle: the largest gap as a share of the factor's largest entry
    (``factor_gap``), and the gap's Frobenius norm as a share of the
    factor's (``factor_fro_gap``)."""
    grid = want.shape[0] // tile
    dev = next(iter(want.devices()))
    rows = []
    for i in range(grid):
        row = []
        for j in range(grid):
            t = tiles.get((i, j))
            row.append(jax.device_put(t, dev) if t is not None else
                       jnp.zeros((tile, tile), want.dtype, device=dev))
        rows.append(row)
    gap, fro = _gaps(jnp.block(rows), want)
    return {"factor_gap": float(gap), "factor_fro_gap": float(fro)}


def kernel_costs(cfg: dict, tile: int) -> dict:
    """Operations and bytes per solve of each task body, as the body
    computes them: ``_update`` is ``c - x @ y.T`` on three ``tile^2`` f32
    operands (read) and one result (written), on the diagonal too."""
    g = cfg["n"] // tile
    updates = sum((g - k - 1) * (g - k) // 2 for k in range(g))
    per = {"_update": (2 * tile ** 3, 4 * tile * tile * 4)}
    return {name: {"tasks": updates, "flops": updates * f,
                   "bytes": updates * b}
            for name, (f, b) in per.items()}


def tasks_per_solve(cfg: dict, tile: int) -> int:
    g = cfg["n"] // tile
    return g + g * (g - 1) // 2 + sum((g - k - 1) * (g - k) // 2
                                      for k in range(g))
