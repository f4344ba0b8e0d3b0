"""Jitted dispatch wrappers for the Pallas kernels.

Model code calls these (via ``ModelOptions.use_flash_kernel`` /
``use_mamba_kernel`` / ``use_paged_kernel``). On a TPU backend every
pl.pallas_call is compiled by Mosaic; on any other backend (the CPU test
suite) it runs in interpret mode (kernel body executed in Python). Nothing
picks interpret mode on a TPU. ``paged_attention`` has its own three-way
lowering switch (``REPRO_PAGED_ATTN``) — see the paged section below.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import mamba_scan as ms
from repro.kernels import paged_attention as pa


def _interpret() -> bool:
    """Interpret off-TPU only: a TPU backend always compiles the kernel."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# flash attention: Pallas forward + flash-style (chunked, rematerialized)
# jnp backward — pallas_call has no AD rule, and the chunked jnp path is the
# memory-optimal backward anyway (recomputes score blocks from (q, k, v)).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, window, kv_offset, block_q, block_k):
    b, sq, hq, hd = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)
    out = fa.flash_attention_bhsd(
        qf, kf, vf, causal=causal, window=window, kv_offset=kv_offset,
        n_q_heads_per_kv=g, block_q=block_q, block_k=block_k,
        interpret=_interpret())
    return out.reshape(b, hq, sq, hd).transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, causal, window, kv_offset, block_q, block_k):
    return _flash_core(q, k, v, causal, window, kv_offset, block_q,
                       block_k), (q, k, v)


def _flash_bwd(causal, window, kv_offset, block_q, block_k, res, ct):
    from repro.models.layers import chunked_attention
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: chunked_attention(
            q, k, v, causal=causal, window=window, kv_offset=kv_offset,
            q_chunk=max(block_q, 128), kv_chunk=max(block_k, 128)),
        q, k, v)
    return vjp(ct)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "kv_offset",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    kv_offset: int = 0, kv_len=None,
                    block_q: int = 512, block_k: int = 512):
    """q (b, sq, hq, hd), k/v (b, sk, hkv, hd) -> (b, sq, hq, hd).

    GQA handled in the kernel's index maps. ``kv_len`` (ragged decode) is not
    kernel-supported; callers use the jnp path for ragged decode.
    """
    if kv_len is not None:
        raise NotImplementedError("ragged kv_len uses the jnp path")
    return _flash_core(q, k, v, causal, window, kv_offset, block_q, block_k)


# ---------------------------------------------------------------------------
# paged attention: forward-only (serving decode/append — no AD path needed)
# attention straight from the block pool through per-row block tables. Three
# lowerings, picked by REPRO_PAGED_ATTN or else by the backend ("pallas" on
# TPU, "jnp" elsewhere):
#   "pallas"    — compiled Pallas kernel (blockspec variant), the TPU target.
#   "interpret" — the Pallas kernel in interpret mode (loop variant); what
#                 the tier-1 parity tests and forced engine parity runs use.
#                 Interpret-mode pallas_call copies every input buffer per
#                 call (O(pool bytes)), so it is for correctness, not speed.
#   "jnp"       — the kernel's XLA mirror (ref.paged_attention_ref): same
#                 block-table-native math; with engine-trimmed tables it does
#                 O(live_blocks) work. The CPU default — this is what makes
#                 the kernel path outrun the gather path off-TPU.
# ---------------------------------------------------------------------------


def _paged_mode() -> str:
    env = os.environ.get("REPRO_PAGED_ATTN")
    if env in ("pallas", "interpret", "jnp"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def paged_attention(q, k_pool, v_pool, block_tables, kv_offset, kv_len, *,
                    causal: bool = True, window: int = 0, q_lens=None):
    """q (b, sq, hq, hd); k/v pool (n_blocks, hkv, block_size, hd);
    block_tables (b, n_tbl) int32 (-1 = unallocated); kv_offset/kv_len (b,)
    per-row cache depth / live length. ``q_lens (b,)`` (optional) is each
    row's real query count in a mixed ragged wave — padded positions emit
    zeros. Returns (b, sq, hq, hd).

    GQA, per-row ragged offsets, kv_len masking and the sliding window are
    all handled in-kernel (see kernels/paged_attention.py); the gathered
    ``max_blocks * block_size`` logical view is never materialized by the
    pallas lowerings, and the jnp mirror only materializes the (trimmed)
    table width it is handed.
    """
    mode = _paged_mode()
    if mode == "jnp":
        from repro.kernels.ref import paged_attention_ref
        return paged_attention_ref(q, k_pool, v_pool, block_tables,
                                   kv_offset, kv_len, causal=causal,
                                   window=window, q_lens=q_lens)
    return pa.paged_attention_pool(
        q, k_pool, v_pool, block_tables,
        jnp.asarray(kv_offset, jnp.int32), jnp.asarray(kv_len, jnp.int32),
        causal=causal, window=window, interpret=(mode == "interpret"),
        q_lens=None if q_lens is None else jnp.asarray(q_lens, jnp.int32))


# ---------------------------------------------------------------------------
# mamba selective scan: Pallas forward + sequential jnp backward (a backward
# Pallas kernel — reverse-time scan with the same chunking — is the natural
# next step; the forward is the serving/inference hot spot).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _mamba_core(da, dbx, cmat, h0, chunk, block_di):
    return ms.mamba_scan_bdn(da, dbx, cmat, h0, chunk=chunk,
                             block_di=block_di, interpret=_interpret())


def _mamba_fwd(da, dbx, cmat, h0, chunk, block_di):
    return _mamba_core(da, dbx, cmat, h0, chunk, block_di), \
        (da, dbx, cmat, h0)


def _mamba_bwd(chunk, block_di, res, ct):
    from repro.kernels.ref import mamba_scan_ref
    da, dbx, cmat, h0 = res
    _, vjp = jax.vjp(mamba_scan_ref, da, dbx, cmat, h0)
    return vjp(ct)


_mamba_core.defvjp(_mamba_fwd, _mamba_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "block_di"))
def mamba_scan(da, dbx, cmat, h0, *, chunk: int = 128, block_di: int = 512):
    """Selective scan: (y, h_final). See mamba_scan.mamba_scan_bdn."""
    di = da.shape[2]
    block = block_di
    while di % block != 0:
        block //= 2
    return _mamba_core(da, dbx, cmat, h0, chunk, max(block, 1))
