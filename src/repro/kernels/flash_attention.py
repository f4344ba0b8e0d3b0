"""Fused flash attention for TPU (Pallas): causal / sliding-window, GQA.

TPU-native adaptation (DESIGN.md §2): the online-softmax recurrence is tiled
for VMEM with MXU-aligned blocks (multiples of 128), the kv dimension is the
innermost *sequential* grid axis with fp32 (m, l, acc) VMEM scratch carried
across kv steps, and GQA is expressed in the BlockSpec index maps (each query
head streams its shared kv head's blocks — no materialized repeat_kv).

Layouts: q (BH, Sq, hd), k/v (BKV, Sk, hd) with BH = batch × q_heads and
BKV = batch × kv_heads. ``ops.flash_attention`` handles the (b, s, h, hd) ↔
grid-layout plumbing, padding and interpret-mode dispatch; ``ref.py`` is the
pure-jnp oracle tested against this kernel across shapes/dtypes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int, kv_offset: int,
                 sq_real: int, sk_real: int, block_q: int, block_k: int,
                 n_kv_blocks: int):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = q_idx * block_q + lax.broadcasted_iota(jnp.int32, (block_q,
                                                              block_k), 0) \
        + kv_offset
    kpos = kv_idx * block_k + lax.broadcasted_iota(jnp.int32, (block_q,
                                                               block_k), 1)
    mask = (kpos < sk_real) & (qpos < sq_real + kv_offset)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    v = v_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kv_idx == n_kv_blocks - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         kv_offset: int = 0, n_q_heads_per_kv: int = 1,
                         block_q: int = 512, block_k: int = 512,
                         interpret: bool = False):
    """Core pallas_call. q (BH, Sq, hd); k/v (BKV, Sk, hd), BH = BKV·group."""
    bh, sq, hd = q.shape
    bkv, sk, _ = k.shape
    g = n_q_heads_per_kv
    assert bh == bkv * g, (bh, bkv, g)
    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    sq_p = -(-sq // block_q) * block_q
    sk_p = -(-sk // block_k) * block_k
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0)))
    n_q = sq_p // block_q
    n_k = sk_p // block_k
    grid = (bh, n_q, n_k)

    kernel = functools.partial(
        _attn_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        window=window, kv_offset=kv_offset, sq_real=sq, sk_real=sk,
        block_q=block_q, block_k=block_k, n_kv_blocks=n_k)

    # GQA in the index maps: query head i streams kv head i // g. The kv/v
    # blocks of one kv head are re-read by its g query heads (VMEM-resident
    # per grid step — no materialized repeat_kv in HBM).
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda i, j, t: (i // g, t, 0)),
            pl.BlockSpec((1, block_k, hd), lambda i, j, t: (i // g, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda i, j, t: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),   # running max m
            pltpu.VMEM((block_q,), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
