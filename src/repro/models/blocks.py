"""Per-family decoder/encoder blocks with functional KV/SSM cache threading.

A *block* is one layer of the stack: pre-norm mixer + pre-norm FFN with
residuals. Signature convention (used by the stacked scan in ``lm.py`` and by
the Hydra pipeline engine):

    y, new_cache = block_apply(cfg, opts, p, x, pos=..., cache=..., mode=...)

``cache`` is this layer's cache slice (or None in train mode); ``pos`` carries
position ids — (b, s) int32 for rope-1d/2d, (3, b, s) for M-RoPE. In decode
mode ``kv_offset`` (b,) gives the current cache length per sequence.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.layers import ModelOptions


# ---------------------------------------------------------------------------
# Attention sub-block (shared by dense / moe / audio / vlm / encoder / hybrid)
# ---------------------------------------------------------------------------


def paged_kv_scatter(cache, k, v, block_tables, kv_offset, write_mask=None):
    """Scatter a (b, s) chunk of new K/V into the shared block pool.

    cache {'k','v'}: (n_blocks, h_kv, block_size, hd) — the *pool*, shared
    by every row (no batch axis), head-major so one (block, head) tile is a
    whole ``(block_size, hd)`` slab (what the TPU paged kernel streams).
    block_tables (b, max_blocks) int32 physical ids local to this shard's
    pool slice, -1 = unallocated. kv_offset (b,) is the row's cache depth
    (tokens already written). ``write_mask`` is (b,) rows or (b, s)
    per-token (mixed ragged waves mask each row's padded tail).
    Masked entries — idle cells riding along, pipeline bubble ticks, or
    ragged query padding — write nothing
    (their scatter indices are pushed out of bounds and dropped); the
    allocator guarantees live rows' blocks are disjoint, so the scatters
    never collide. Tokens past table capacity (``pos // bs >= max_blocks``)
    are dropped too — clipping the block index instead would alias them onto
    the row's *last* allocated block (the clipped entry holds a valid
    physical id, so the ``phys >= 0`` check alone lets the write land) and
    silently corrupt cached K/V. Returns the updated pool.
    """
    b, s = k.shape[0], k.shape[1]
    nb, bs = cache["k"].shape[0], cache["k"].shape[2]
    max_blocks = block_tables.shape[1]
    # scatter the chunk: token i of row r lands in block table[r, p//bs] at
    # in-block slot p%bs, p = kv_offset[r] + i
    pos = kv_offset[:, None] + jnp.arange(s)[None, :]  # (b, s)
    blk = jnp.clip(pos // bs, 0, max_blocks - 1)
    phys = jnp.take_along_axis(block_tables, blk, axis=1)  # (b, s)
    ok = (phys >= 0) & (pos // bs < max_blocks)
    if write_mask is not None:
        ok = ok & (write_mask if write_mask.ndim == 2 else write_mask[:, None])
    phys = jnp.where(ok, phys, nb).reshape(-1)  # OOB block -> dropped
    slot = (pos % bs).reshape(-1)

    def scat(pool, t):  # t (b, s, h_kv, hd) -> pool[phys, :, slot]
        return pool.at[phys, :, slot].set(
            t.reshape(b * s, *t.shape[2:]).astype(pool.dtype), mode="drop")

    return {"k": scat(cache["k"], k), "v": scat(cache["v"], v)}


def paged_kv_update(cache, k, v, block_tables, kv_offset, write_mask=None):
    """Scatter (see :func:`paged_kv_scatter`) and gather each row's full
    logical cache view back out through its table.

    Returns (new_cache, k_rows, v_rows) where k_rows/v_rows are
    (b, max_blocks*block_size, h_kv, hd) gathered views whose garbage tail
    (unallocated blocks / stale tokens) the caller masks via kv_len. This is
    the *gather path* — O(max_blocks·block_size) materialized per row per
    call; the paged kernel path (``opts.use_paged_kernel``) scatters only and
    attends straight from the pool.
    """
    b = k.shape[0]
    nb, hkv, bs, hd = cache["k"].shape
    max_blocks = block_tables.shape[1]
    new_cache = paged_kv_scatter(cache, k, v, block_tables, kv_offset,
                                 write_mask)
    # gather each row's logical view: position j reads block table[r, j//bs]
    phys = jnp.clip(block_tables, 0, nb - 1)

    def gather(pool):  # (b, max_blocks, h_kv, bs, hd) -> token-major rows
        return (jnp.take(pool, phys, axis=0).transpose(0, 1, 3, 2, 4)
                .reshape(b, max_blocks * bs, hkv, hd))

    return new_cache, gather(new_cache["k"]), gather(new_cache["v"])


def attn_apply(cfg: ArchConfig, opts: ModelOptions, p, x, *, pos,
               cache=None, kv_offset=None, mode: str = "train",
               window: int = 0, causal: bool = True, block_tables=None,
               write_mask=None, q_lens=None):
    """x (b, s, d) -> (b, s, d); cache {'k','v'}: (b, S_max, h_kv, hd).

    ``block_tables`` switches the append/decode cache handling to the paged
    pool layout (see :func:`paged_kv_update`): cache is then the shared
    (n_blocks, h_kv, block_size, hd) pool and ``write_mask`` gates which rows
    may write this call.

    ``q_lens (b,)`` activates the mixed-tick ragged-wave semantics in append
    mode: each row's real query count (chunk width for prefilling cells, 1
    for decoding cells, 0 for idle), with positions past it padding — never
    written to the cache, attending to nothing. A decode row is exactly the
    ``q_lens = 1`` case of append, so one program serves both phases.
    """
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(b, s, h, hd)
    k = jnp.einsum("bsd,de->bse", x, p["wk"]).reshape(b, s, hkv, hd)
    v = jnp.einsum("bsd,de->bse", x, p["wv"]).reshape(b, s, hkv, hd)
    q = L.apply_rope(q, pos, cfg)
    k = L.apply_rope(k, pos, cfg)
    new_cache = cache
    if mode == "train":
        out = L.attention(q, k, v, causal=causal, window=window, opts=opts)
    elif mode == "prefill":
        # write k/v into the cache (offset 0); windowed caches keep the tail
        s_cache = cache["k"].shape[1]
        if s >= s_cache:
            kw, vw = k[:, -s_cache:], v[:, -s_cache:]
            pad = 0
        else:
            kw, vw, pad = k, v, s_cache - s
        new_cache = {
            "k": jnp.pad(kw, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(
                cache["k"].dtype),
            "v": jnp.pad(vw, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(
                cache["v"].dtype),
        }
        out = L.attention(q, k, v, causal=causal, window=window, opts=opts)
    elif mode == "append" and block_tables is not None:
        # paged chunked prefill: same semantics as the dense append below but
        # K/V live in the shared block pool, reached through per-row tables
        cap = block_tables.shape[1] * cache["k"].shape[2]
        kv_len = jnp.minimum(kv_offset + (s if q_lens is None else q_lens),
                             cap)
        wm = write_mask
        if q_lens is not None:
            # mixed ragged wave: only each row's first q_lens tokens are real
            tok = jnp.arange(s)[None, :] < q_lens[:, None]
            if wm is not None:
                tok = tok & (wm if wm.ndim == 2 else wm[:, None])
            wm = tok
        if opts.use_paged_kernel:
            # scatter only — the kernel attends straight from the pool
            # through the tables, never building the gathered view
            from repro.kernels import ops as kernel_ops
            new_cache = paged_kv_scatter(cache, k, v, block_tables,
                                         kv_offset, wm)
            out = kernel_ops.paged_attention(
                q, new_cache["k"], new_cache["v"], block_tables, kv_offset,
                kv_len, causal=True, window=window, q_lens=q_lens)
        else:
            new_cache, kf, vf = paged_kv_update(cache, k, v, block_tables,
                                                kv_offset, wm)
            out = L.attention(
                q, kf.astype(q.dtype), vf.astype(q.dtype),
                causal=True, window=window, kv_offset=kv_offset,
                kv_len=kv_len, opts=opts)
    elif mode == "append":
        # chunked prefill: insert a whole chunk at kv_offset and attend over
        # the cache prefix + causally within the chunk (kv_offset handles the
        # relative positions). kv_offset is per-row (b,) — rows may sit at
        # different cache depths (continuous-batching admission chunks).
        s_cache = cache["k"].shape[1]
        if q_lens is not None:
            # mixed ragged wave: rows are padded to the wave max, and
            # ``dynamic_update_slice`` CLAMPS out-of-range starts — a decode
            # row near the strip end would have its padded write shifted
            # backwards over real history. Scatter per token instead,
            # dropping padded and out-of-strip targets.
            tgt = kv_offset[:, None] + jnp.arange(s)[None, :]  # (b, s)
            ok = (jnp.arange(s)[None, :] < q_lens[:, None]) & (tgt < s_cache)
            if write_mask is not None:
                ok = ok & (write_mask if write_mask.ndim == 2
                           else write_mask[:, None])
            flat = jnp.where(ok, jnp.arange(b)[:, None] * s_cache + tgt,
                             b * s_cache)  # OOB -> dropped

            def scat(c, t):
                cf = c.reshape(b * s_cache, *c.shape[2:])
                cf = cf.at[flat.reshape(-1)].set(
                    t.reshape(b * s, *t.shape[2:]).astype(c.dtype),
                    mode="drop")
                return cf.reshape(c.shape)
            new_cache = {"k": scat(cache["k"], k), "v": scat(cache["v"], v)}
            kv_len = jnp.minimum(kv_offset + q_lens, s_cache)
        else:
            def updm(c, t, o):
                return lax.dynamic_update_slice(c, t.astype(c.dtype),
                                                (o, 0, 0))
            new_cache = {
                "k": jax.vmap(updm)(cache["k"], k, kv_offset),
                "v": jax.vmap(updm)(cache["v"], v, kv_offset),
            }
            kv_len = jnp.minimum(kv_offset + s, s_cache)
        out = L.attention(
            q, new_cache["k"].astype(q.dtype), new_cache["v"].astype(q.dtype),
            causal=True, window=window, kv_offset=kv_offset,
            kv_len=kv_len, opts=opts)
    elif mode == "decode" and block_tables is not None:
        # paged decode: one-token append through the table, then the same
        # masked-full-cache attention the dense decode runs; window > 0
        # additionally masks positions <= pos - window (the gathered view is
        # in absolute logical layout, so the positional mask is exact)
        cap = block_tables.shape[1] * cache["k"].shape[2]
        kv_len = jnp.minimum(kv_offset + 1, cap)
        if opts.use_paged_kernel:
            # kernel decode is causal with per-row offsets: at sq=1 the mask
            # kpos <= kv_offset & kpos < kv_len equals the gather path's
            # causal=False kv_len-only mask
            from repro.kernels import ops as kernel_ops
            new_cache = paged_kv_scatter(cache, k, v, block_tables,
                                         kv_offset, write_mask)
            out = kernel_ops.paged_attention(
                q, new_cache["k"], new_cache["v"], block_tables, kv_offset,
                kv_len, causal=True, window=window)
        elif window > 0:
            new_cache, kf, vf = paged_kv_update(cache, k, v, block_tables,
                                                kv_offset, write_mask)
            out = L.attention(
                q, kf.astype(q.dtype), vf.astype(q.dtype),
                causal=True, window=window, kv_offset=kv_offset,
                kv_len=kv_len, opts=opts)
        else:
            new_cache, kf, vf = paged_kv_update(cache, k, v, block_tables,
                                                kv_offset, write_mask)
            out = L.attention(
                q, kf.astype(q.dtype), vf.astype(q.dtype),
                causal=False, window=0, kv_offset=0, kv_len=kv_len, opts=opts)
    elif mode == "decode":
        # ring-buffer insert: slot = kv_offset mod cache_len (identity for
        # unwindowed caches, rolling slot for sliding-window caches)
        s_cache = cache["k"].shape[1]
        slot = kv_offset % s_cache

        def upd(c, t, o):
            return lax.dynamic_update_slice(c, t.astype(c.dtype), (o, 0, 0))
        new_cache = {
            "k": jax.vmap(upd)(cache["k"], k, slot),
            "v": jax.vmap(upd)(cache["v"], v, slot),
        }
        kv_len = jnp.minimum(kv_offset + 1, s_cache)
        if window > 0 and s_cache > window:
            # absolute-layout cache wider than the window (continuous-batching
            # serving keeps max_seq strips): mask positions <= pos - window.
            # When s_cache <= window the ring itself enforces the window (the
            # static long-context path) and every live row is attendable.
            out = L.attention(
                q, new_cache["k"].astype(q.dtype),
                new_cache["v"].astype(q.dtype), causal=True, window=window,
                kv_offset=kv_offset, kv_len=kv_len, opts=opts)
        else:
            out = L.attention(
                q, new_cache["k"].astype(q.dtype),
                new_cache["v"].astype(q.dtype),
                causal=False, window=0, kv_offset=0, kv_len=kv_len, opts=opts)
    else:
        raise ValueError(mode)
    out = out.reshape(b, s, h * hd)
    return jnp.einsum("bse,ed->bsd", out, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# Family blocks
# ---------------------------------------------------------------------------


def dense_block(cfg, opts, p, x, *, pos, cache=None, kv_offset=None,
                mode="train", window: int = 0, block_tables=None,
                write_mask=None, q_lens=None):
    causal = cfg.family != "encoder"
    if cfg.family == "encoder":
        h = L.layer_norm(x, p["ln1_w"], p["ln1_b"], cfg.norm_eps)
    else:
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    a, new_cache = attn_apply(cfg, opts, p["attn"], h, pos=pos, cache=cache,
                              kv_offset=kv_offset, mode=mode, window=window,
                              causal=causal, block_tables=block_tables,
                              write_mask=write_mask, q_lens=q_lens)
    x = x + a
    if cfg.family == "encoder":
        h = L.layer_norm(x, p["ln2_w"], p["ln2_b"], cfg.norm_eps)
    else:
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + L.mlp_apply(p["mlp"], h, cfg.act)
    return x, new_cache, jnp.zeros((), jnp.float32)


def moe_block(cfg, opts, p, x, *, pos, cache=None, kv_offset=None,
              mode="train", window: int = 0, block_tables=None,
              write_mask=None, q_lens=None):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    a, new_cache = attn_apply(cfg, opts, p["attn"], h, pos=pos, cache=cache,
                              kv_offset=kv_offset, mode=mode, window=window,
                              block_tables=block_tables,
                              write_mask=write_mask, q_lens=q_lens)
    x = x + a
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    m, aux = L.moe_apply(p["moe"], h, n_experts=cfg.moe.n_experts,
                         top_k=cfg.moe.top_k,
                         capacity_factor=opts.moe_capacity_factor,
                         act=cfg.act, expert_chunk=opts.moe_expert_chunk)
    return x + m, new_cache, aux


def ssm_block(cfg, opts, p, x, *, pos, cache=None, kv_offset=None,
              mode="train", window: int = 0, block_tables=None,
              write_mask=None, q_lens=None):
    """Mamba1 block (falcon-mamba): norm -> mamba -> residual.
    (``block_tables``/``write_mask``/``q_lens`` are accepted for signature
    uniformity; recurrent state is O(1) per row and never paged, and ragged
    mixed waves are attention-family only — padded tokens would advance the
    recurrent state.)"""
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    ssm_s = cache["ssm"] if cache is not None else None
    conv_s = cache["conv"] if cache is not None else None
    y, new_ssm, new_conv = L.mamba1_mix(p["mamba"], h, cfg, ssm_s, conv_s,
                                        opts)
    new_cache = None
    if cache is not None:
        new_cache = {"ssm": new_ssm, "conv": new_conv.astype(cache["conv"].dtype)}
    return x + y, new_cache, jnp.zeros((), jnp.float32)


def hybrid_backbone_block(cfg, opts, p, x, *, pos, cache=None, kv_offset=None,
                          mode="train", window: int = 0, block_tables=None,
                          write_mask=None, q_lens=None):
    """Zamba2 backbone layer: Mamba2 mixer. (Paging kwargs unused: the
    recurrent state is O(1) per row.)"""
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    ssm_s = cache["ssm"] if cache is not None else None
    conv_s = cache["conv"] if cache is not None else None
    y, new_ssm, new_conv = L.mamba2_mix(p["mamba"], h, cfg, ssm_s, conv_s,
                                        opts)
    new_cache = None
    if cache is not None:
        new_cache = {"ssm": new_ssm, "conv": new_conv.astype(cache["conv"].dtype)}
    return x + y, new_cache, jnp.zeros((), jnp.float32)


def shared_attn_block(cfg, opts, p, x, *, pos, cache=None, kv_offset=None,
                      mode="train", window: int = 0):
    """Zamba2's shared attention+MLP block (weights shared across sites)."""
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    a, new_cache = attn_apply(cfg, opts, p["attn"], h, pos=pos, cache=cache,
                              kv_offset=kv_offset, mode=mode, window=window)
    x = x + a
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + L.mlp_apply(p["mlp"], h, "swiglu")
    return x, new_cache


BLOCK_FNS = {
    "dense": dense_block,
    "audio": dense_block,
    "vlm": dense_block,
    "encoder": dense_block,
    "moe": moe_block,
    "ssm": ssm_block,
    "hybrid": hybrid_backbone_block,
}


def block_fn_for(cfg: ArchConfig):
    return BLOCK_FNS[cfg.family]


# ---------------------------------------------------------------------------
# Per-layer cache structure (shapes only — used for init and dry-run specs)
# ---------------------------------------------------------------------------


def layer_cache_shape(cfg: ArchConfig, batch: int, max_seq: int,
                      cache_dtype=jnp.bfloat16, block_size: int = 0) -> dict:
    """Shape/dtype template for ONE layer's cache (no leading layer dim).

    ``block_size > 0`` gives the paged pool instead (attention families):
    ``batch`` blocks of ``block_size`` tokens, head-major
    ``(n_blocks, h_kv, block_size, hd)`` (see :func:`paged_kv_scatter`).
    """
    if block_size > 0:
        pool = jax.ShapeDtypeStruct(
            (batch, cfg.n_kv_heads, block_size, cfg.head_dim), cache_dtype)
        return {"k": pool, "v": pool}
    if cfg.family == "ssm":
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        return {
            "ssm": jax.ShapeDtypeStruct((batch, di, s.d_state), jnp.float32),
            "conv": jax.ShapeDtypeStruct((batch, s.d_conv - 1, di), cache_dtype),
        }
    if cfg.family == "hybrid":
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        nh = s.n_ssm_heads(cfg.d_model)
        conv_dim = di + 2 * s.n_groups * s.d_state
        return {
            "ssm": jax.ShapeDtypeStruct(
                (batch, nh, s.head_dim, s.d_state), jnp.float32),
            "conv": jax.ShapeDtypeStruct(
                (batch, s.d_conv - 1, conv_dim), cache_dtype),
        }
    return {
        "k": jax.ShapeDtypeStruct(
            (batch, max_seq, cfg.n_kv_heads, cfg.head_dim), cache_dtype),
        "v": jax.ShapeDtypeStruct(
            (batch, max_seq, cfg.n_kv_heads, cfg.head_dim), cache_dtype),
    }


def shared_cache_shape(cfg: ArchConfig, batch: int, max_seq: int,
                       cache_dtype=jnp.bfloat16,
                       window: int = 0) -> Optional[dict]:
    """Cache template for ONE shared-attention site (hybrid archs).

    ``window`` > 0 (long-context serving) bounds the cache to the sliding
    window; the engine activates it only for the long_500k shape.
    """
    if cfg.hybrid is None:
        return None
    seq = min(max_seq, window) if window > 0 else max_seq
    return {
        "k": jax.ShapeDtypeStruct(
            (batch, seq, cfg.n_kv_heads, cfg.head_dim), cache_dtype),
        "v": jax.ShapeDtypeStruct(
            (batch, seq, cfg.n_kv_heads, cfg.head_dim), cache_dtype),
    }
