"""Chunked Mamba1 selective scan for TPU (Pallas).

The recurrence h_t = da_t ⊙ h_{t-1} + dbx_t with per-(channel, state) decay is
sequential in time but parallel over (batch, d_inner, d_state). TPU-native
tiling (DESIGN.md §2): grid (batch, d_inner blocks, time chunks) with the time
chunk as the innermost *sequential* axis; the (bdi, n) state lives in fp32
VMEM scratch across chunk steps, each chunk streams (ck, bdi, n) decay/input
tiles HBM→VMEM once and emits the contracted output y = Σ_n h·C directly —
the (b, s, di, n) hidden history is never materialized in HBM (the pure-jnp
path's dominant memory cost).

Layouts: da/dbx (b, s, di, n), cmat (b, s, n), y (b, s, di), h0/h_out
(b, di, n).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(h0_ref, da_ref, dbx_ref, c_ref, y_ref, hout_ref, h_ref, *,
                 chunk: int, n_chunks: int, s_real: int):
    t_idx = pl.program_id(2)

    @pl.when(t_idx == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    da = da_ref[0].astype(jnp.float32)    # (ck, bdi, n)
    dbx = dbx_ref[0].astype(jnp.float32)  # (ck, bdi, n)
    c = c_ref[0].astype(jnp.float32)      # (ck, n)

    def step(i, carry):
        h = carry
        t_global = t_idx * chunk + i
        valid = t_global < s_real
        da_t = jnp.where(valid, da[i], 1.0)   # padded steps: identity decay
        dbx_t = jnp.where(valid, dbx[i], 0.0)
        h = da_t * h + dbx_t
        y_t = jnp.sum(h * c[i][None, :], axis=-1)  # (bdi,)
        y_ref[0, i] = y_t.astype(y_ref.dtype)
        return h

    h = lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h

    @pl.when(t_idx == n_chunks - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def mamba_scan_bdn(da, dbx, cmat, h0, *, chunk: int = 128,
                   block_di: int = 512, interpret: bool = False):
    """da/dbx (b, s, di, n); cmat (b, s, n); h0 (b, di, n) →
    (y (b, s, di), h_final (b, di, n))."""
    b, s, di, n = da.shape
    block_di = min(block_di, di)
    assert di % block_di == 0, (di, block_di)
    chunk = min(chunk, s)
    s_p = -(-s // chunk) * chunk
    if s_p != s:
        pad = ((0, 0), (0, s_p - s), (0, 0), (0, 0))
        da = jnp.pad(da, pad)
        dbx = jnp.pad(dbx, pad)
        cmat = jnp.pad(cmat, ((0, 0), (0, s_p - s), (0, 0)))
    n_chunks = s_p // chunk
    n_di = di // block_di
    grid = (b, n_di, n_chunks)

    kernel = functools.partial(_scan_kernel, chunk=chunk, n_chunks=n_chunks,
                               s_real=s)
    # blocks move time-major so the sequential grid axis streams chunks
    y, h_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_di, n), lambda bi, d, t: (bi, d, 0)),
            pl.BlockSpec((1, chunk, block_di, n),
                         lambda bi, d, t: (bi, t, d, 0)),
            pl.BlockSpec((1, chunk, block_di, n),
                         lambda bi, d, t: (bi, t, d, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, d, t: (bi, t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_di), lambda bi, d, t: (bi, t, d)),
            pl.BlockSpec((1, block_di, n), lambda bi, d, t: (bi, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s_p, di), da.dtype),
            jax.ShapeDtypeStruct((b, di, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_di, n), jnp.float32)],
        interpret=interpret,
    )(h0, da, dbx, cmat)
    return y[:, :s], h_out
