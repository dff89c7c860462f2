"""Pure-jnp oracle for the tiled matmul benchmark.

Precision: the tile dots run at ``Precision.HIGHEST``.  On the TPU an f32
dot at the default precision rounds both operands to bf16 for one MXU
pass (8 significant bits), which is not the f32 arithmetic the apps'
numerics checks are written for; HIGHEST keeps f32 accuracy (multi-pass
on the MXU, fp32 contraction inside a Pallas kernel).  On the CPU an f32
dot is f32 at any precision, so nothing changes there.
"""
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b, c=None):
    """``c + a @ b`` (``c`` defaults to zero), f32 accumulation."""
    out = jnp.matmul(a, b, preferred_element_type=jnp.float32,
                     precision=HIGHEST)
    if c is not None:
        out = c.astype(jnp.float32) + out
    return out.astype(a.dtype)


def tile_update(c, a, b):
    """Cholesky-style trailing update: ``c - a @ b^T`` (f32 accumulation)."""
    prod = jnp.matmul(a, b.T, preferred_element_type=jnp.float32,
                      precision=HIGHEST)
    return (c.astype(jnp.float32) - prod).astype(c.dtype)
