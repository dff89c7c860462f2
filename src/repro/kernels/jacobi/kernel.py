"""Pallas TPU kernel: one Jacobi 5-point sweep over a 2-D grid.

Halo exchange via BlockSpecs: the grid tiles rows; three input specs map the
*same* array at block rows (i-1, i, i+1) (clamped at the edges), so each
program sees its block plus the neighbouring row blocks already staged in
VMEM — the TPU analogue of the SCC tasks reading neighbour tiles from shared
DRAM.  Columns stay whole (the paper's 512-wide tiles fit VMEM: 3 blocks x
block_rows x width x 4 B).  Boundary rows/cols are kept fixed with iota
masks on the *global* row index.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _jacobi_kernel(top_ref, mid_ref, bot_ref, out_ref, *, block_rows: int,
                   n_rows: int):
    i = pl.program_id(0)
    x = mid_ref[...]
    bm, w = x.shape
    # neighbour rows: from the adjacent blocks (clamped to self at the edges)
    up = jnp.concatenate([top_ref[...][-1:, :], x[:-1, :]], axis=0)
    down = jnp.concatenate([x[1:, :], bot_ref[...][:1, :]], axis=0)
    left = jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)
    right = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)
    stencil = 0.25 * (up + down + left + right)
    # Dirichlet boundary: global first/last rows and first/last cols fixed
    grow = i * block_rows + jax.lax.broadcasted_iota(jnp.int32, (bm, w), 0)
    gcol = jax.lax.broadcasted_iota(jnp.int32, (bm, w), 1)
    boundary = ((grow == 0) | (grow == n_rows - 1) |
                (gcol == 0) | (gcol == w - 1))
    out_ref[...] = jnp.where(boundary, x, stencil)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def jacobi_step_pallas(x, *, block_rows: int = 256, interpret: bool = False):
    n_rows, width = x.shape
    block_rows = min(block_rows, n_rows)
    if n_rows % block_rows:
        raise ValueError(f"rows {n_rows} not divisible by {block_rows}")
    n_blocks = n_rows // block_rows
    spec = lambda off: pl.BlockSpec(
        (block_rows, width),
        lambda i, _off=off: (jnp.clip(i + _off, 0, n_blocks - 1), 0))
    return pl.pallas_call(
        functools.partial(_jacobi_kernel, block_rows=block_rows,
                          n_rows=n_rows),
        grid=(n_blocks,),
        in_specs=[spec(-1), spec(0), spec(+1)],
        out_specs=pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, width), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, x, x)
