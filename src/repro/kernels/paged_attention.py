"""Paged flash attention for TPU (Pallas): decode/append straight from the
block pool — no gathered logical K/V view.

The serve stack's paged path (``blocks.paged_kv_update``) scatters new K/V
into a shared head-major ``(n_blocks, h_kv, block_size, hd)`` pool and then
*gathers*
each row's full ``max_blocks * block_size`` logical view before running
dense attention — O(max_seq) HBM traffic per decode step regardless of the
row's actual ``kv_len``. This kernel removes the gather: attention reads K/V
directly from the pool through each row's block table, touching only the
blocks that hold live tokens.

Layout & grid
    q is packed ``(b, h_kv, g·sq, hd)`` (the ``g`` query heads sharing one kv
    head ride as extra rows — GQA without a materialized repeat_kv), carrying
    fp32 (m, l, acc) online-softmax state across physical blocks exactly like
    ``flash_attention.py``. Two bodies share the per-block accumulate step:

    * ``variant="blockspec"`` — grid ``(b, h_kv, n_tbl)`` with the table axis
      innermost *sequential* and (m, l, acc) in VMEM scratch; the K/V
      BlockSpec index maps stream one physical ``(block_size, hd)`` tile
      into VMEM per step. This is the TPU compile target: the pool
      indirection is resolved by the pipeline before each body runs, so it
      costs index arithmetic, not a gathered copy. The head-major pool puts
      ``(block_size, hd)`` in the two minor dims, so each streamed tile is
      whole in the dims Mosaic tiles by (8, 128) — a ``(block_size, h_kv,
      hd)`` pool would make the block one head out of the second-minor dim,
      which the TPU lowering refuses.
    * ``variant="loop"`` — grid ``(b, h_kv)`` with the whole pool left in
      ``ANY`` memory and an in-kernel ``fori_loop`` from the first windowed
      block to ``ceil(kv_len / block_size)``, reading each live physical
      block by table entry (ref indexing). This is the interpret-mode/CPU
      execution path
      (far fewer grid steps; per-row cost scales with live length and is
      flat in table width). On TPU the same structure needs the loads
      replaced by double-buffered ``make_async_copy`` — the noted next step.

Scalar-prefetch scheme
    ``block_tables (b, n_tbl)``, ``kv_offset (b,)``, ``kv_len (b,)`` and
    ``q_lens (b,)`` are scalar-prefetched
    (``pltpu.PrefetchScalarGridSpec``): the blockspec variant's K/V index
    maps read ``block_tables[ib, t]`` to pick the physical block for grid
    step (ib, ·, t), the loop variant reads the same tables inside the
    body. Unallocated entries (-1) are clamped to block 0 and neutralized
    by the masks below.

Masking semantics (all in-kernel, per row ib)
    * ``kpos >= kv_len[ib]`` — stale pool tokens / unallocated tail: masked.
    * causal: ``kpos <= kv_offset[ib] + q_row`` (per-row ragged offsets —
      rows of one call may sit at different cache depths).
    * ``q_row >= q_lens[ib]`` — mixed-tick ragged padding: a wave packs
      rows of different chunk widths to one ``sq``; a row's padded query
      positions attend to nothing and emit zeros (decode rows are the
      ``q_lens = 1`` case, idle rows ``q_lens = 0``).
    * sliding window > 0: ``kpos > qpos - window``.
    * table steps with no live position (``t·block_size >= kv_len[ib]``, or
      wholly below the window) are skipped — ``pl.when`` in the blockspec
      variant, the loop bounds in the loop variant — so decode cost scales
      with the row's live length, not the table width.

``ops.paged_attention`` handles layout packing, row padding and
interpret-mode dispatch; ``ref.paged_attention_ref`` is the gather-then-
attend oracle both variants are swept against in
tests/test_kernels_paged.py.
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


def _accumulate(q, k, v, t, off, kv_end, q_len, m_prev, l_prev, acc_prev, *,
                scale, causal, window, block_size, sq_real, rows_real):
    """One online-softmax step over physical block ``t`` (all fp32).

    q (rows, hd), k/v (block_size, hd); returns updated (m, l, acc).
    ``q_len`` masks ragged query padding (mixed-tick waves); fully masked
    rows keep m at NEG_INF so they finalize to zeros. Shared by both kernel
    variants so the masking semantics cannot drift.
    """
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    rows = s.shape[0]
    ri = lax.broadcasted_iota(jnp.int32, (rows, block_size), 0)
    qi = ri % sq_real  # row = head_in_group * sq_real + query_index
    kpos = t * block_size + lax.broadcasted_iota(
        jnp.int32, (rows, block_size), 1)
    qpos = off + qi
    mask = (kpos < kv_end) & (ri < rows_real) & (qi < q_len)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.where(mask, jnp.exp(s - m_safe[:, None]), 0.0)
    alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
    acc = acc_prev * alpha[:, None] + lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return m_new, l_prev * alpha + jnp.sum(p, axis=-1), acc


def _paged_kernel(tbl_ref, off_ref, len_ref, ql_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
                  window: int, block_size: int, sq_real: int, rows_real: int,
                  n_tbl: int):
    """Blockspec variant body: one grid step = one table entry."""
    ib = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off = off_ref[ib]
    kv_end = len_ref[ib]
    q_len = ql_ref[ib]
    # skip table steps with no attendable position: past the row's live
    # length, or (windowed) wholly below every query's window
    live = (t * block_size) < kv_end
    if window > 0:
        live &= (t * block_size + block_size + window) > (off + 1)

    @pl.when(live)
    def _accum():
        m_ref[...], l_ref[...], acc_ref[...] = _accumulate(
            q_ref[0, 0].astype(jnp.float32),
            k_ref[0, 0].astype(jnp.float32),
            v_ref[0, 0].astype(jnp.float32),
            t, off, kv_end, q_len, m_ref[...], l_ref[...], acc_ref[...],
            scale=scale, causal=causal, window=window, block_size=block_size,
            sq_real=sq_real, rows_real=rows_real)

    @pl.when(t == n_tbl - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _paged_kernel_loop(tbl_ref, off_ref, len_ref, ql_ref, q_ref, k_ref,
                       v_ref, o_ref, *, scale: float, causal: bool,
                       window: int, block_size: int, sq_real: int,
                       rows_real: int, rows: int, hd: int):
    """Loop variant body: fori_loop over the row's live table entries."""
    ib = pl.program_id(0)
    ih = pl.program_id(1)
    off = off_ref[ib]
    kv_end = len_ref[ib]
    q_len = ql_ref[ib]
    q = q_ref[0, 0].astype(jnp.float32)

    def body(t, carry):
        m, l, acc = carry
        phys = jnp.maximum(tbl_ref[ib, t], 0)
        return _accumulate(
            q, k_ref[phys, ih].astype(jnp.float32),
            v_ref[phys, ih].astype(jnp.float32),
            t, off, kv_end, q_len, m, l, acc, scale=scale, causal=causal,
            window=window, block_size=block_size, sq_real=sq_real,
            rows_real=rows_real)

    t_start = 0
    if window > 0:
        # first table entry any query can still see: qpos_min - window + 1
        t_start = jnp.maximum(off - window + 1, 0) // block_size
    n_live = lax.div(kv_end + block_size - 1, block_size)
    m, l, acc = lax.fori_loop(
        t_start, n_live, body,
        (jnp.full((rows,), NEG_INF, jnp.float32),
         jnp.zeros((rows,), jnp.float32),
         jnp.zeros((rows, hd), jnp.float32)))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def paged_attention_pool(q, k_pool, v_pool, block_tables, kv_offset, kv_len,
                         *, causal: bool = True, window: int = 0,
                         interpret: bool = False, variant: str | None = None,
                         q_lens=None):
    """Core pallas_call. q (b, sq, hq, hd); k/v pool (n_blocks, h_kv,
    block_size, hd); block_tables (b, n_tbl) int32 physical ids (-1 unallocated);
    kv_offset/kv_len (b,) int32. Returns (b, sq, hq, hd).

    ``q_lens (b,)`` (optional) gives each row's real query count for mixed
    ragged waves — positions ``>= q_lens[ib]`` are padding and emit zeros;
    ``None`` means every row uses all ``sq`` positions.

    ``variant`` defaults to "loop" under interpret (CPU) and "blockspec"
    compiled (TPU). Rows whose table holds no live blocks (kv_len 0 / fully
    masked) emit zeros — idle serve cells riding along are discarded
    upstream.
    """
    if variant is None:
        variant = "loop" if interpret else "blockspec"
    b, sq, hq, hd = q.shape
    if q_lens is None:
        q_lens = jnp.full((b,), sq, jnp.int32)
    nb, hkv, bs, _ = k_pool.shape
    n_tbl = block_tables.shape[1]
    g = hq // hkv
    assert hq == hkv * g, (hq, hkv)
    # pack GQA groups as rows: (b, hkv, g*sq, hd), row = ig*sq + iq, then
    # pad the row dim up to the dtype's min sublane tile
    qp = q.transpose(0, 2, 1, 3).reshape(b, hkv, g * sq, hd)
    rows_real = g * sq
    mult = 16 if q.dtype == jnp.bfloat16 else 8
    rows = -(-rows_real // mult) * mult
    if rows != rows_real:
        qp = jnp.pad(qp, ((0, 0), (0, 0), (0, rows - rows_real), (0, 0)))

    common = dict(scale=1.0 / math.sqrt(hd), causal=causal, window=window,
                  block_size=bs, sq_real=sq, rows_real=rows_real)
    if variant == "loop":
        kernel = functools.partial(_paged_kernel_loop, rows=rows, hd=hd,
                                   **common)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, hkv),
            in_specs=[
                pl.BlockSpec((1, 1, rows, hd),
                             lambda ib, ih, tbl, off, ln, ql: (ib, ih, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, hd),
                                   lambda ib, ih, tbl, off, ln, ql:
                                   (ib, ih, 0, 0)),
        )
    else:
        kernel = functools.partial(_paged_kernel, n_tbl=n_tbl, **common)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, hkv, n_tbl),
            in_specs=[
                pl.BlockSpec((1, 1, rows, hd),
                             lambda ib, ih, t, tbl, off, ln, ql:
                             (ib, ih, 0, 0)),
                # the pool indirection: table entry t of row ib names the
                # physical block streamed at grid step (ib, ih, t); -1 clamps
                # to block 0 (its positions are masked via kv_len)
                pl.BlockSpec((1, 1, bs, hd),
                             lambda ib, ih, t, tbl, off, ln, ql:
                             (jnp.maximum(tbl[ib, t], 0), ih, 0, 0)),
                pl.BlockSpec((1, 1, bs, hd),
                             lambda ib, ih, t, tbl, off, ln, ql:
                             (jnp.maximum(tbl[ib, t], 0), ih, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, hd),
                                   lambda ib, ih, t, tbl, off, ln, ql:
                                   (ib, ih, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows,), jnp.float32),     # running max m
                pltpu.VMEM((rows,), jnp.float32),     # running denom l
                pltpu.VMEM((rows, hd), jnp.float32),  # output accumulator
            ],
        )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, hd), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), kv_offset.astype(jnp.int32),
      kv_len.astype(jnp.int32), q_lens.astype(jnp.int32), qp, k_pool, v_pool)
    return (out[:, :, :rows_real]
            .reshape(b, hkv, g, sq, hd)
            .transpose(0, 3, 1, 2, 4)
            .reshape(b, sq, hq, hd))
