"""Stacked-layer language models: init, forward, decode — pure JAX.

The layer stack is stored with a leading layer axis on every leaf so that
(a) ``lax.scan`` applies layers with O(1) HLO size, and (b) the Hydra pipeline
engine can shard that axis across pipeline stages (`PartitionSpec('model', …)`)
and run a *contiguous slice* of layers per stage via the same ``stack_apply``.

``stack_apply`` therefore takes a per-layer validity ``mask`` (stages pad the
layer count to stages × layers_per_stage) and, for hybrid archs, per-layer
shared-attention site flags. Single-device forward (= the exactness oracle) is
just ``stack_apply`` over all layers with mask all-true.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchConfig
from repro.models import blocks as B
from repro.models import layers as L
from repro.models.layers import ModelOptions


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape) / math.sqrt(max(fan_in, 1))).astype(dtype)


def init_attn_params(cfg: ArchConfig, key, dtype):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": _normal(k1, (d, h * hd), d, dtype),
        "wk": _normal(k2, (d, hkv * hd), d, dtype),
        "wv": _normal(k3, (d, hkv * hd), d, dtype),
        "wo": _normal(k4, (h * hd, d), h * hd, dtype),
    }


def init_mlp_params(d: int, f: int, act: str, key, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    if act == "swiglu":
        return {"w_gate": _normal(k1, (d, f), d, dtype),
                "w_up": _normal(k2, (d, f), d, dtype),
                "w_down": _normal(k3, (f, d), f, dtype)}
    return {"w_up": _normal(k1, (d, f), d, dtype),
            "w_down": _normal(k2, (f, d), f, dtype)}


def init_layer_params(cfg: ArchConfig, key, dtype):
    """One layer of the stack (no leading layer dim)."""
    d = cfg.d_model
    if cfg.family == "ssm":
        s = cfg.ssm
        di, n = s.d_inner(d), s.d_state
        r = s.resolved_dt_rank(d)
        ks = jax.random.split(key, 6)
        # dt bias ~ softplus^-1 of dt in [1e-3, 1e-1] (mamba init)
        u = jax.random.uniform(ks[5], (di,), minval=math.log(1e-3),
                               maxval=math.log(1e-1))
        dt = jnp.exp(u)
        dt_bias = dt + jnp.log1p(-jnp.exp(-dt))
        return {
            "ln": jnp.ones((d,), dtype),
            "mamba": {
                "in_proj": _normal(ks[0], (d, 2 * di), d, dtype),
                "conv_w": _normal(ks[1], (di, s.d_conv), s.d_conv, dtype),
                "conv_b": jnp.zeros((di,), dtype),
                "x_proj": _normal(ks[2], (di, r + 2 * n), di, dtype),
                "dt_proj": _normal(ks[3], (r, di), r, dtype),
                "dt_bias": dt_bias.astype(jnp.float32),
                "A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, n + 1, dtype=jnp.float32), (di, n))),
                "D": jnp.ones((di,), jnp.float32),
                "out_proj": _normal(ks[4], (di, d), di, dtype),
            },
        }
    if cfg.family == "hybrid":
        s = cfg.ssm
        di, n, g = s.d_inner(d), s.d_state, s.n_groups
        nh = s.n_ssm_heads(d)
        conv_dim = di + 2 * g * n
        ks = jax.random.split(key, 4)
        u = jax.random.uniform(ks[3], (nh,), minval=math.log(1e-3),
                               maxval=math.log(1e-1))
        dt = jnp.exp(u)
        dt_bias = dt + jnp.log1p(-jnp.exp(-dt))
        return {
            "ln": jnp.ones((d,), dtype),
            "mamba": {
                "in_proj": _normal(ks[0], (d, 2 * di + 2 * g * n + nh), d, dtype),
                "conv_w": _normal(ks[1], (conv_dim, s.d_conv), s.d_conv, dtype),
                "conv_b": jnp.zeros((conv_dim,), dtype),
                "dt_bias": dt_bias.astype(jnp.float32),
                "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
                "D": jnp.ones((nh,), jnp.float32),
                "norm_w": jnp.ones((di,), dtype),
                "out_proj": _normal(ks[2], (di, d), di, dtype),
            },
        }
    # attention families
    k_attn, k_mlp, k_moe = jax.random.split(key, 3)
    p = {"attn": init_attn_params(cfg, k_attn, dtype)}
    if cfg.family == "encoder":
        p["ln1_w"] = jnp.ones((cfg.d_model,), dtype)
        p["ln1_b"] = jnp.zeros((cfg.d_model,), dtype)
        p["ln2_w"] = jnp.ones((cfg.d_model,), dtype)
        p["ln2_b"] = jnp.zeros((cfg.d_model,), dtype)
    else:
        p["ln1"] = jnp.ones((cfg.d_model,), dtype)
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.family == "moe":
        e, fe = cfg.moe.n_experts, cfg.moe.expert_d_ff
        km = jax.random.split(k_moe, 4)
        p["moe"] = {
            "router": _normal(km[0], (d, e), d, dtype),
            "w_gate": _normal(km[1], (e, d, fe), d, dtype),
            "w_up": _normal(km[2], (e, d, fe), d, dtype),
            "w_down": _normal(km[3], (e, fe, d), fe, dtype),
        }
    else:
        p["mlp"] = init_mlp_params(d, cfg.d_ff, cfg.act, k_mlp, dtype)
    return p


def init_shared_params(cfg: ArchConfig, key, dtype):
    if cfg.hybrid is None:
        return None
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": init_attn_params(cfg, k1, dtype),
        "mlp": init_mlp_params(cfg.d_model, cfg.hybrid.shared_d_ff, "swiglu",
                               k2, dtype),
    }


def init_params(cfg: ArchConfig, key, dtype=jnp.float32, max_pos: int = 0,
                n_layers: Optional[int] = None):
    """Full model pytree. Layer leaves get a leading ``n_layers`` axis.

    ``n_layers`` may exceed ``cfg.n_layers`` (stage padding); padded layers
    get ordinary init but are masked out at apply time.
    """
    nl = n_layers or cfg.n_layers
    k_emb, k_layers, k_shared, k_head, k_pos = jax.random.split(key, 5)
    layer_keys = jax.random.split(k_layers, nl)
    layers = jax.vmap(lambda k: init_layer_params(cfg, k, dtype))(layer_keys)
    params = {
        "embed": {"tok": _normal(k_emb, (cfg.vocab_size, cfg.d_model), 1, dtype)},
        "layers": layers,
        "final_norm": (
            {"w": jnp.ones((cfg.d_model,), dtype),
             "b": jnp.zeros((cfg.d_model,), dtype)}
            if cfg.family == "encoder" else jnp.ones((cfg.d_model,), dtype)),
    }
    if cfg.rope == "learned":
        params["embed"]["pos"] = _normal(k_pos, (max(max_pos, 1), cfg.d_model),
                                         1, dtype)
    if cfg.hybrid is not None:
        params["shared"] = init_shared_params(cfg, k_shared, dtype)
    if not cfg.tie_embeddings:
        params["head"] = _normal(k_head, (cfg.d_model, cfg.vocab_size),
                                 cfg.d_model, dtype)
    return params


# ---------------------------------------------------------------------------
# Shared-site bookkeeping (hybrid archs)
# ---------------------------------------------------------------------------


def shared_site_flags(cfg: ArchConfig, layer_offset: int, n_local: int):
    """(use_shared, site_slot) int arrays for layers [offset, offset+n_local).

    ``site_slot`` is the *local* slot index within this stage's shared-cache
    buffer (sequential over the stage's flagged layers).
    """
    if cfg.hybrid is None:
        return (jnp.zeros((n_local,), bool), jnp.zeros((n_local,), jnp.int32))
    gidx = layer_offset + jnp.arange(n_local)  # offset may be traced (stage id)
    flags = ((gidx + 1) % cfg.hybrid.attn_every == 0) & (gidx < cfg.n_layers)
    slots = jnp.cumsum(flags.astype(jnp.int32)) - 1
    return flags, jnp.maximum(slots, 0)


def n_shared_sites(cfg: ArchConfig, layer_offset: int = 0,
                   n_local: Optional[int] = None) -> int:
    if cfg.hybrid is None:
        return 0
    n_local = n_local if n_local is not None else cfg.n_layers
    count = 0
    for g in range(layer_offset, layer_offset + n_local):
        if (g + 1) % cfg.hybrid.attn_every == 0 and g < cfg.n_layers:
            count += 1
    return max(count, 1)


# ---------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------


def _zeros_like_spec(tree):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tree)


def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               cache_dtype=jnp.bfloat16, n_layers: Optional[int] = None,
               window: int = 0):
    """Stacked per-layer cache (leading layer axis) + shared-site cache."""
    nl = n_layers or cfg.n_layers
    one = B.layer_cache_shape(cfg, batch, max_seq, cache_dtype)
    stacked = jax.tree.map(
        lambda s: jnp.zeros((nl,) + s.shape, s.dtype), one)
    shared = None
    if cfg.hybrid is not None:
        s_one = B.shared_cache_shape(cfg, batch, max_seq, cache_dtype, window)
        ns = n_shared_sites(cfg)
        shared = jax.tree.map(
            lambda s: jnp.zeros((ns,) + s.shape, s.dtype), s_one)
    return {"layers": stacked, "shared": shared}


def cache_specs(cfg: ArchConfig, batch: int, max_seq: int,
                cache_dtype=jnp.bfloat16, n_layers: Optional[int] = None,
                window: int = 0, n_shared_slots: Optional[int] = None):
    """ShapeDtypeStruct view of ``init_cache`` (dry-run, no allocation)."""
    nl = n_layers or cfg.n_layers
    one = B.layer_cache_shape(cfg, batch, max_seq, cache_dtype)
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((nl,) + s.shape, s.dtype), one)
    shared = None
    if cfg.hybrid is not None:
        s_one = B.shared_cache_shape(cfg, batch, max_seq, cache_dtype, window)
        ns = n_shared_slots if n_shared_slots is not None else n_shared_sites(cfg)
        shared = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((ns,) + s.shape, s.dtype), s_one)
    return {"layers": stacked, "shared": shared}


# ---------------------------------------------------------------------------
# Stacked layer application (the unit the pipeline engine runs per stage)
# ---------------------------------------------------------------------------

# checkpoint name of each layer's input in a rematerialized train stack
# (``name_inputs``): an enclosing ``jax.checkpoint`` whose policy saves it
# keeps them, so its backward need not run the stack again to regain them
LAYER_INPUT = "layer_input"


def stack_apply(cfg: ArchConfig, opts: ModelOptions, layer_params, x, *,
                pos, mode: str = "train", cache=None, shared_params=None,
                shared_cache=None, layer_mask=None, layer_offset=0,
                kv_offset=None, window: int = 0, layer_param_fn=None,
                inner_remat=None, name_inputs=False, block_tables=None,
                write_mask=None, q_lens=None):
    """Apply a contiguous slice of the layer stack.

    layer_params: pytree with leading local-layer axis (n_local, ...).
    cache:        {"layers": stacked cache or None, "shared": site cache}.
    layer_mask:   (n_local,) bool — False = padded no-op layer.
    layer_param_fn: optional hook applied to each layer's params inside the
        scan body (the pipeline engine uses it for per-layer FSDP all-gather).
    inner_remat:  checkpoint each layer in train mode (default
        ``opts.remat``); with ``name_inputs`` each layer's input is then
        named ``LAYER_INPUT``.
    ``layer_offset`` may be a traced scalar (stage_id * layers_per_stage).
    ``block_tables``/``write_mask`` switch append/decode attention to the
    paged cache layout (cache["layers"] then stacks per-layer block *pools*
    with no batch axis — see ``blocks.paged_kv_update``); ``q_lens (b,)``
    carries per-row real query counts for mixed ragged append waves.
    Returns (y, new_cache, aux_loss_sum).
    """
    n_local = jax.tree.leaves(layer_params)[0].shape[0]
    if layer_mask is None:
        layer_mask = jnp.ones((n_local,), bool)
    use_shared, site_slot = shared_site_flags(cfg, layer_offset, n_local)
    block = B.block_fn_for(cfg)
    layer_cache = cache["layers"] if cache is not None else None
    sh_cache = cache["shared"] if cache is not None else shared_cache
    has_cache = layer_cache is not None

    def body(carry, xs):
        xc, shc, aux = carry
        if has_cache:
            p_i, m_i, us_i, slot_i, c_i = xs
        else:
            p_i, m_i, us_i, slot_i = xs
            c_i = None
        if layer_param_fn is not None:
            p_i = layer_param_fn(p_i)

        def run(operand):
            xc, shc, c_i = operand
            y, new_c, aux_i = block(cfg, opts, p_i, xc, pos=pos, cache=c_i,
                                    kv_offset=kv_offset, mode=mode,
                                    window=window, block_tables=block_tables,
                                    write_mask=write_mask, q_lens=q_lens)
            if shared_params is not None:
                def run_shared(op):
                    y, shc = op
                    sc_i = None
                    if shc is not None:
                        sc_i = jax.tree.map(lambda c: c[slot_i], shc)
                    y2, new_sc = B.shared_attn_block(
                        cfg, opts, shared_params, y, pos=pos, cache=sc_i,
                        kv_offset=kv_offset, mode=mode, window=window)
                    if shc is not None:
                        shc = jax.tree.map(
                            lambda c, n: lax.dynamic_update_index_in_dim(
                                c, n.astype(c.dtype), slot_i, 0),
                            shc, new_sc)
                    return y2, shc

                y, shc2 = lax.cond(us_i, run_shared, lambda op: op, (y, shc))
            else:
                shc2 = shc
            return y, shc2, (new_c if new_c is not None else c_i), aux_i

        def skip(operand):
            xc, shc, c_i = operand
            return xc, shc, c_i, jnp.zeros((), jnp.float32)

        y, shc_new, c_new, aux_i = lax.cond(m_i, run, skip, (xc, shc, c_i))
        return (y, shc_new, aux + aux_i), c_new

    do_remat = opts.remat if inner_remat is None else inner_remat
    body_fn = body
    if do_remat and mode == "train":
        body_fn = layer_fn = jax.checkpoint(body)
        if name_inputs:
            def body_fn(carry, xs):
                xc, shc, aux = carry
                return layer_fn((checkpoint_name(xc, LAYER_INPUT), shc, aux),
                                xs)
    xs = (layer_params, layer_mask, use_shared, site_slot)
    if has_cache:
        xs = xs + (layer_cache,)
    (y, sh_new, aux), cache_new = lax.scan(body_fn, (x, sh_cache, 0.0), xs)
    out_cache = None
    if has_cache:
        out_cache = {"layers": cache_new, "shared": sh_new}
    return y, out_cache, aux


# ---------------------------------------------------------------------------
# Embedding / head / loss
# ---------------------------------------------------------------------------


def embed_tokens(cfg: ArchConfig, p_embed, tokens, *, positions=None,
                 frontend_embeds=None, compute_dtype=None):
    x = jnp.take(p_embed["tok"], tokens, axis=0)
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
    if cfg.rope == "learned" and positions is not None:
        pos_table = p_embed["pos"]
        x = x + jnp.take(pos_table, jnp.minimum(positions, pos_table.shape[0] - 1),
                         axis=0).astype(x.dtype)
    if frontend_embeds is not None:
        nf = frontend_embeds.shape[1]
        x = x.at[:, :nf].set(frontend_embeds.astype(x.dtype))
    return x


def final_norm_apply(cfg: ArchConfig, p_norm, x):
    if cfg.family == "encoder":
        return L.layer_norm(x, p_norm["w"], p_norm["b"], cfg.norm_eps)
    return L.rms_norm(x, p_norm, cfg.norm_eps)


def lm_logits(cfg: ArchConfig, params, x):
    x = final_norm_apply(cfg, params["final_norm"], x)
    head = params.get("head")
    if head is None:  # tied embeddings
        head = params["embed"]["tok"].T
    return jnp.einsum("bsd,dv->bsv", x, head)


def cross_entropy(logits, labels, mask=None):
    """Mean CE over unmasked positions; fp32 accumulation."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Whole-model entry points (single-device oracle; smoke tests; examples)
# ---------------------------------------------------------------------------


def default_positions(cfg: ArchConfig, batch: dict, b: int, s: int):
    if cfg.rope == "mrope":
        if "mrope_pos" in batch:
            return batch["mrope_pos"]
        base = jnp.broadcast_to(jnp.arange(s), (b, s))
        return jnp.broadcast_to(base, (3, b, s))
    return jnp.broadcast_to(jnp.arange(s), (b, s))


def forward(cfg: ArchConfig, opts: ModelOptions, params, batch: dict,
            mode: str = "train", cache=None, kv_offset=None, window: int = 0,
            layer_mask=None):
    """Full-model forward. Returns (logits, new_cache, aux).

    ``layer_mask`` supports stage-padded stacks (leaves longer than
    cfg.n_layers); defaults to masking exactly the real layers.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    if mode == "decode":
        pos = kv_offset[:, None]  # (b, 1) absolute positions
        if cfg.rope == "mrope":
            pos = jnp.broadcast_to(pos, (3, b, 1))
    else:
        pos = default_positions(cfg, batch, b, s)
    x = embed_tokens(cfg, params["embed"], tokens,
                     positions=pos if cfg.rope != "mrope" else None,
                     frontend_embeds=batch.get("frontend_embeds"),
                     compute_dtype=opts.compute_dtype)
    n_stack = jax.tree.leaves(params["layers"])[0].shape[0]
    if layer_mask is None and n_stack != cfg.n_layers:
        layer_mask = jnp.arange(n_stack) < cfg.n_layers
    y, new_cache, aux = stack_apply(
        cfg, opts, params["layers"], x, pos=pos, mode=mode, cache=cache,
        shared_params=params.get("shared"), layer_offset=0,
        kv_offset=kv_offset, window=window, layer_mask=layer_mask)
    logits = lm_logits(cfg, params, y)
    return logits, new_cache, aux


def loss_fn(cfg: ArchConfig, opts: ModelOptions, params, batch: dict):
    logits, _, aux = forward(cfg, opts, params, batch, mode="train")
    loss = cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    if cfg.moe is not None:
        loss = loss + cfg.moe.load_balance_coef * aux / max(cfg.n_layers, 1)
    return loss


# ---------------------------------------------------------------------------
# The paper's 1.2M-param feed-forward workload (uniform hidden stack so it
# maps onto the same embed/stage/head pipeline structure).
# ---------------------------------------------------------------------------


def mlp_init(mlp_cfg, key, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    hidden_keys = jax.random.split(ks[1], mlp_cfg.n_hidden)

    def one(k):
        kw, = jax.random.split(k, 1)
        return {"w": _normal(kw, (mlp_cfg.d_hidden, mlp_cfg.d_hidden),
                             mlp_cfg.d_hidden, dtype),
                "b": jnp.zeros((mlp_cfg.d_hidden,), dtype)}

    return {
        "embed": {"w": _normal(ks[0], (mlp_cfg.d_in, mlp_cfg.d_hidden),
                               mlp_cfg.d_in, dtype),
                  "b": jnp.zeros((mlp_cfg.d_hidden,), dtype)},
        "layers": jax.vmap(one)(hidden_keys),
        "head": {"w": _normal(ks[2], (mlp_cfg.d_hidden, mlp_cfg.d_out),
                              mlp_cfg.d_hidden, dtype),
                 "b": jnp.zeros((mlp_cfg.d_out,), dtype)},
    }


def mlp_forward(params, x, layer_mask=None):
    h = jax.nn.relu(x @ params["embed"]["w"] + params["embed"]["b"])

    def body(carry, xs):
        if layer_mask is None:
            p = xs
            return jax.nn.relu(carry @ p["w"] + p["b"]), None
        p, m = xs
        y = jax.nn.relu(carry @ p["w"] + p["b"])
        return jnp.where(m, y, carry), None

    xs = params["layers"] if layer_mask is None else (params["layers"], layer_mask)
    h, _ = lax.scan(body, h, xs)
    return h @ params["head"]["w"] + params["head"]["b"]


def mlp_loss(params, batch):
    logits = mlp_forward(params, batch["x"])
    return cross_entropy(logits[:, None, :], batch["y"][:, None])
