"""Pallas TPU kernel: blocked matmul ``c + a @ b``.

Grid = (M/bm, N/bn, K/bk), K innermost with "arbitrary" semantics so the
(bm, bn) output block stays resident in VMEM across the K sweep; a float32
VMEM scratch accumulator feeds the MXU via ``jnp.dot(...,
preferred_element_type=f32)``.  Block sizes default to (128, 128, 128) —
MXU-aligned (the systolic array is 128x128) and a working set of
3 * 128*128*4B = 192 KiB, comfortably inside the ~16 MiB/core VMEM with room
for double-buffered pipelining of the next A/B blocks.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(a_ref, b_ref, c_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(jnp.float32)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_pallas(a, b, c, *, bm: int = 128, bn: int = 128, bk: int = 128,
                  interpret: bool = False):
    """``c + a @ b`` with (M,K)x(K,N); M,N,K divisible by the block sizes."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and c.shape == (m, n)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"dims {(m, n, k)} not divisible by blocks "
                         f"{(bm, bn, bk)}")
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_mm_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), c.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, c)


def _update_kernel(c_ref, a_ref, b_ref, out_ref, acc_ref, *, n_k: int):
    """Trailing-update form ``c - a @ b^T`` (B arrives untransposed)."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(jnp.float32)

    acc_ref[...] -= jnp.dot(a_ref[...], b_ref[...].T,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def tile_update_pallas(c, a, b, *, bk: int = 128, interpret: bool = False):
    """``c - a @ b^T`` for (m,k)x(n,k) tiles — the Cholesky/SYRK update."""
    m, k = a.shape
    n, k2 = b.shape
    assert k == k2 and c.shape == (m, n)
    bk = min(bk, k)
    if k % bk:
        raise ValueError(f"k={k} not divisible by bk={bk}")
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_update_kernel, n_k=n_k),
        grid=(1, n_k),
        in_specs=[
            pl.BlockSpec((m, n), lambda i, kk: (0, 0)),
            pl.BlockSpec((m, bk), lambda i, kk: (0, kk)),
            pl.BlockSpec((n, bk), lambda i, kk: (0, kk)),
        ],
        out_specs=pl.BlockSpec((m, n), lambda i, kk: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), c.dtype),
        scratch_shapes=[pltpu.VMEM((m, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(c, a, b)
