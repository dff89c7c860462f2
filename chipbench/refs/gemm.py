"""Inputs, plain reference, kernel costs and check of tiled GEMM.

Nothing here imports the system under test.  The reference is one
untiled ``C0 + A @ B`` in plain ``jax.numpy``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.refs import dots

INPUTS = ("A", "B", "C")


def make_inputs(key, cfg: dict) -> dict:
    """Three standard normal ``n x n`` f32 matrices, made on the device."""
    n = cfg["n"]

    @jax.jit
    def gen(key):
        ka, kb, kc = jax.random.split(key, 3)
        return {k: jax.random.normal(kk, (n, n), jnp.float32)
                for k, kk in zip(INPUTS, (ka, kb, kc))}

    return gen(key)


@functools.partial(jax.jit, static_argnames="dot")
def _product(a, b, c, *, dot):
    return c + dot(a, b)


def reference(inputs: dict, cfg: dict, dot=dots.highest):
    return _product(inputs["A"], inputs["B"], inputs["C"], dot=dot)


def output_indices(grid: int) -> list[tuple[int, int]]:
    """Every tile of ``C``."""
    return [(i, j) for i in range(grid) for j in range(grid)]


@jax.jit
def _gaps(got, want):
    d = got - want
    return (jnp.max(jnp.abs(d)) / jnp.max(jnp.abs(want)),
            jnp.linalg.norm(d) / jnp.linalg.norm(want))


def compare(tiles: dict, want, tile: int) -> dict:
    """The largest gap over ``C`` as a share of its largest entry
    (``product_gap``), and the gap's Frobenius norm as a share of ``C``'s
    (``product_fro_gap``)."""
    grid = want.shape[0] // tile
    dev = next(iter(want.devices()))
    got = jnp.block([[jax.device_put(tiles[i, j], dev)
                      for j in range(grid)] for i in range(grid)])
    gap, fro = _gaps(got, want)
    return {"product_gap": float(gap), "product_fro_gap": float(fro)}


def kernel_costs(cfg: dict, tile: int) -> dict:
    """``_gemm`` is ``c + x @ y`` on three ``tile^2`` f32 operands (read)
    and one result (written); a solve runs ``(n / tile)^3`` of them."""
    g = cfg["n"] // tile
    tasks = g ** 3
    return {"_gemm": {"tasks": tasks, "flops": tasks * 2 * tile ** 3,
                      "bytes": tasks * 4 * tile * tile * 4}}


def tasks_per_solve(cfg: dict, tile: int) -> int:
    return (cfg["n"] // tile) ** 3
