"""``repro.dist`` — mesh context and sharding helpers.

The model code is written against three tiny hooks so it runs unchanged
from a single-device pytest to a multi-pod mesh:

* :func:`use_mesh` / :func:`current` — install / read the ambient
  :class:`~repro.dist.context.MeshContext`;
* :func:`constrain_seq` — pin a (B, S, d) activation's batch dim to the
  batch axes;
* :func:`constrain_heads` — pin a (B, H, S, D) attention tensor's head
  dim to the model axis (tensor parallelism) and batch dim to the data
  axes.

Both constraints are divisibility-guarded no-ops without a mesh, so
importing this module never forces a distribution choice.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .context import MeshContext
from . import sharding  # noqa: F401

__all__ = ["MeshContext", "use_mesh", "current", "constrain_seq",
           "constrain_heads", "sharding", "single_device_mesh"]

_state = threading.local()


def current() -> MeshContext | None:
    """The innermost active mesh context, or None."""
    stack = getattr(_state, "stack", None)
    return stack[-1] if stack else None


def single_device_mesh(axis: str = "data"):
    """A one-device mesh over the default local device.

    This is the smallest mesh the mesh-dependent code paths accept — the
    :class:`~repro.core.sharded.ShardedExecutor`'s per-owner dispatch, the
    sharding rules, ``placement.device_assignment`` — so single-device
    tests and CI exercise the *same* code the real mesh runs, not a
    separate branch.  Install it with :func:`use_mesh`.
    """
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]), (axis,))


@contextlib.contextmanager
def use_mesh(mesh, **ctx_kw):
    """Activate ``mesh`` (with axis roles per ``MeshContext``) for the
    dynamic extent of the block; yields the context."""
    ctx = MeshContext(mesh, **ctx_kw)
    stack = getattr(_state, "stack", None)
    if stack is None:
        stack = _state.stack = []
    stack.append(ctx)
    try:
        yield ctx
    finally:
        stack.pop()


def _constrain(x, spec_builder):
    ctx = current()
    if ctx is None:
        return x
    spec = spec_builder(ctx, x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def constrain_seq(x):
    """(B, S, d) activations: batch over the full batch axes."""
    def build(ctx, shape):
        if len(shape) < 2:
            return None
        axes = ctx.batch_axes_full
        if shape[0] % ctx.full_batch_size() != 0:
            return None
        return P(axes, *([None] * (len(shape) - 1)))
    return _constrain(x, build)


def constrain_heads(x):
    """(B, H, S, D) attention tensors: heads over the model axis, batch
    over the data axes."""
    def build(ctx, shape):
        if len(shape) != 4 or ctx.model_in_batch:
            return None
        b = ctx.all_data_axes if shape[0] % ctx.dp_size() == 0 else None
        m = ctx.model_axis \
            if shape[1] % ctx.axis_size(ctx.model_axis) == 0 else None
        if b is None and m is None:
            return None
        return P(b, m, None, None)
    return _constrain(x, build)
