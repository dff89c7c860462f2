"""Pallas TPU kernel: FlashAttention-style blocked causal attention.

Grid = (B*Hq, Sq/bq, Skv/bk), kv innermost ("arbitrary" semantics) so the
running max / sum / accumulator persist in VMEM scratch across the kv sweep
(the online-softmax recurrence).  GQA is free: the K/V BlockSpec index maps
divide the head coordinate by the group size, so shared KV blocks are
fetched once per group without materializing repeated heads in HBM.

Block sizes default to (bq, bk) = (256, 256): the MXU sees (256, D)x(D, 256)
and (256, 256)x(256, D) matmuls; the VMEM working set is
q + k + v + acc + p ~ 5 * 256*128*4B ~ 0.7 MiB, leaving headroom for the
pipeline's double buffering.  Fully-masked causal blocks are skipped with
``pl.when`` — on TPU the block's DMas still run but the MXU work is elided.

m/l statistics live in (bq, 128) lane-replicated scratch, the standard
Mosaic-friendly layout for row statistics.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30  # avoids -inf - -inf = nan in fully-masked rows


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, n_k: int, causal: bool, scale: float,
                  kv_offset: int):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal block skip: block (iq, ik) participates iff its first kv pos
    # can be visible to its last q pos
    first_k = ik * bk
    last_q = iq * bq + bq - 1 + kv_offset
    run = (first_k <= last_q) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)             # (bq, d)
        k = k_ref[0].astype(jnp.float32)             # (bk, d)
        v = v_ref[0].astype(jnp.float32)             # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
                + kv_offset
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, _NEG_INF)
        m_prev = m_ref[:, :1]                        # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)    # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)              # (bq, 1)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows -> 0
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret", "scale"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           scale: float | None = None, bq: int = 256,
                           bk: int = 256, interpret: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D).

    Causal masking aligns the query suffix to the kv end (Sq == Skv in
    training; Sq < Skv for chunked prefill continuation).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    bq = min(bq, sq)
    bk = min(bk, skv)
    if sq % bq or skv % bk:
        raise ValueError(f"seq lens {(sq, skv)} not divisible by {(bq, bk)}")
    scale = scale if scale is not None else float(d) ** -0.5
    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hkv, skv, d)
    vr = v.reshape(b * hkv, skv, d)
    n_k = skv // bk
    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, n_k=n_k,
                          causal=causal, scale=scale, kv_offset=skv - sq),
        grid=(b * hq, sq // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda h, iq, ik, _g=group: (h // _g, ik, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda h, iq, ik, _g=group: (h // _g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max m
            pltpu.VMEM((bq, 128), jnp.float32),   # running sum l
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d)
