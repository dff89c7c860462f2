"""The two f32 products the references are computed with.

``highest`` is the precision the configurations state: f32 operands at
``Precision.HIGHEST``, which the TPU's MXU runs as six bf16 passes.

``three_pass`` is the check's control, the next precision below:
``Precision.HIGH``, three bf16 passes.  On a TPU that is the MXU's own
three-pass product.  Elsewhere an f32 dot ignores the precision flag, so
the passes are written out: each operand split into a bf16 high part and
a bf16 low part, and the three products of the parts that ``HIGH`` keeps,
summed in f32.  (Written out and run on a TPU v5e, the split read like a
one-pass product: it did not survive compilation there.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def highest(a, b):
    return jnp.matmul(a, b, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


def _split(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def three_pass(a, b):
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGH)
    (ah, al), (bh, bl) = _split(a), _split(b)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))
