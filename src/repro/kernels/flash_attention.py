"""Pallas TPU kernel: blocked (flash) attention forward, causal / sliding
window / logit-softcap (gemma2/3) -- the serving attention for the 32k
prefill and long-context decode shapes.

Canonical online-softmax structure: grid = (B*H, Tq/bq, S/bk); the innermost
grid dim walks KV blocks while (acc, m, l) live in VMEM scratch across steps
(output block revisiting).  Per grid step VMEM = bq*hd + 2*bk*hd + bq*bk
floats; bq=bk=128-aligned for the MXU.  GQA is handled by the wrapper
(q heads grouped per kv head); backward is by design NOT provided -- training
uses the query-chunked XLA attention (models/blocks._attend) whose gradients
come from autodiff under remat (DESIGN.md §6).

Oracle: kernels/ref.attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import pallas_call

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int | None,
                  softcap: float | None, bq: int, bk: int, seq_k: int,
                  q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                     # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                     # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T) * scale                          # (bq, bk)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    qpos = qi * bq + jax.lax.iota(jnp.int32, bq)[:, None] + q_offset
    kpos = ki * bk + jax.lax.iota(jnp.int32, bk)[None, :]
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softcap: float | None = None, scale: float | None = None,
                    bq: int = 128, bk: int = 128):
    """q: (B, H, Tq, hd); k, v: (B, Hkv, S, hd) with H % Hkv == 0.

    Returns (B, H, Tq, hd).  Query positions are aligned to the END of the
    key sequence (decode-friendly): q_offset = S - Tq.
    """
    b, h, tq, hd = q.shape
    _, hkv, s, _ = k.shape
    g = h // hkv
    scale = scale if scale is not None else hd ** -0.5
    tq_p = (tq + bq - 1) // bq * bq
    s_p = (s + bk - 1) // bk * bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, tq_p - tq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, s_p - s), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, s_p - s), (0, 0)))
    # fold batch+head into grid dim 0; map q head -> kv head
    qf = qp.reshape(b * h, tq_p, hd)
    kf = kp.reshape(b * hkv, s_p, hd)
    vf = vp.reshape(b * hkv, s_p, hd)

    grid = (b * h, tq_p // bq, s_p // bk)

    def q_map(i, j, kk):
        return (i, j, 0)

    def kv_map_fn(i, j, kk):
        bb = i // h
        hh = i % h
        return (bb * hkv + hh // g, kk, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, seq_k=s, q_offset=s - tq)
    out = pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bq, hd), q_map),
                  pl.BlockSpec((1, bk, hd), kv_map_fn),
                  pl.BlockSpec((1, bk, hd), kv_map_fn)],
        out_specs=pl.BlockSpec((1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, tq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((bq, 1), jnp.float32),    # l (running denom)
        ],
    )(qf, kf, vf)
    return out.reshape(b, h, tq_p, hd)[:, :, :tq]
