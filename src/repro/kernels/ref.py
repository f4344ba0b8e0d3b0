"""Pure-jnp oracles for the Pallas kernels (single source of truth for the
allclose sweeps in tests/test_kernels_*.py)."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.models.layers import attention_reference


def flash_attention_ref(q, k, v, *, causal=True, window=0, kv_offset=0,
                        kv_len=None):
    """q/k/v (b, s, h, hd) — direct-softmax oracle (fp32 math)."""
    return attention_reference(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32),
                               causal=causal, window=window,
                               kv_offset=kv_offset, kv_len=kv_len
                               ).astype(q.dtype)


def paged_attention_ref(q, k_pool, v_pool, block_tables, kv_offset, kv_len,
                        *, causal=True, window=0, q_lens=None):
    """Gather-then-attend oracle for the paged kernel (fp32 math).

    k/v pool (n_blocks, h_kv, block_size, hd), head-major as the serve
    cache holds it. Materializes each row's full logical K/V view through
    its block table (the exact path ``blocks.paged_kv_update`` takes) and
    runs the direct-softmax reference over it — the kernel must match this on live
    positions while never building the gathered view. ``q_lens (b,)``
    mirrors the kernel's ragged-wave semantics: query positions past a
    row's real count are zeroed.
    """
    nb, hkv, bs, hd = k_pool.shape
    b = q.shape[0]
    span = (jnp.clip(block_tables, 0, nb - 1)[:, :, None] * bs
            + jnp.arange(bs)[None, None, :]).reshape(b, -1)

    def token_major(pool):  # (nb, hkv, bs, hd) -> (nb * bs, hkv, hd)
        return pool.transpose(0, 2, 1, 3).reshape(nb * bs, hkv, hd)

    kf = jnp.take(token_major(k_pool), span, axis=0)
    vf = jnp.take(token_major(v_pool), span, axis=0)
    out = attention_reference(q.astype(jnp.float32), kf.astype(jnp.float32),
                              vf.astype(jnp.float32), causal=causal,
                              window=window, kv_offset=kv_offset,
                              kv_len=kv_len)
    if q_lens is not None:
        pad = jnp.arange(q.shape[1])[None, :] < q_lens[:, None]
        out = jnp.where(pad[:, :, None, None], out, 0.0)
    return out.astype(q.dtype)


def mamba_scan_ref(da, dbx, cmat, h0):
    """Sequential oracle: h_t = da_t*h + dbx_t; y_t = Σ_n h_t C_t.

    da/dbx (b, s, di, n), cmat (b, s, n), h0 (b, di, n).
    """
    def step(h, inp):
        da_t, dbx_t, c_t = inp
        h = da_t.astype(jnp.float32) * h + dbx_t.astype(jnp.float32)
        y = jnp.sum(h * c_t[:, None, :].astype(jnp.float32), axis=-1)
        return h, y

    h, ys = lax.scan(step, h0.astype(jnp.float32),
                     (da.swapaxes(0, 1), dbx.swapaxes(0, 1),
                      cmat.swapaxes(0, 1)))
    return ys.swapaxes(0, 1).astype(da.dtype), h
