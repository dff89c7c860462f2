"""Pallas TPU kernel: flash-decode over one KV shard.

Grid = (B, Hkv, S/bk), kv innermost.  Each program handles the G = Hq/Hkv
query heads that share a KV head: q block (1, 1, G, D) against kv blocks
(1, 1, bk, D).  G x bk and G x D matmuls are thin — decode is HBM-bandwidth
bound, and the kernel's job is to stream K/V through VMEM exactly once
(the explicit DMA pipeline standing in for the paper's invalidate-read
fences).  Emits the shard-normalized output and the log-sum-exp so shards
striped across devices combine exactly (see ref.combine_partials).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                   acc_ref, *, n_k: int, scale: float):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (G, d)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, _NEG_INF, m_ref[:, :1] + jnp.log(safe_l))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref[0, 0].shape)


@functools.partial(jax.jit, static_argnames=("bk", "interpret", "scale"))
def flash_decode_pallas(q, k, v, *, scale: float | None = None,
                        bk: int = 512, interpret: bool = False):
    """q: (B, Hq, D); k, v: (B, Hkv, S, D) -> (o (B,Hq,D) f32, lse (B,Hq))."""
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    g = hq // hkv
    bk = min(bk, s)
    if s % bk:
        raise ValueError(f"kv length {s} not divisible by block {bk}")
    scale = scale if scale is not None else float(d) ** -0.5
    qr = q.reshape(b, hkv, g, d)
    n_k = s // bk
    o, lse = pl.pallas_call(
        functools.partial(_decode_kernel, n_k=n_k, scale=scale),
        grid=(b, hkv, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda ib, ih, ik: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik: (ib, ih, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda ib, ih, ik: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, g, 128), lambda ib, ih, ik: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, k, v)
    return o.reshape(b, hq, d), lse[..., 0].reshape(b, hq)
