"""Plain reference of chatglm3-6b as the benchmark runs it: jax.numpy in
float32 at the highest matmul precision, no cache, no batching tricks, no
kernels. It imports nothing of the program; it reads the sizes from
``chatglm3-6b.json`` and the weights by their names in the parameter tree
(``embed/tok``, ``layers/attn/wq``, ...), trial ``k``.

It runs layer by layer, one row at a time and queries in blocks, so that it
fits beside the served weights on one chip.

``precision="fp8"`` is the control: every matrix product's operands are
rounded to float8 (e4m3) with one scale per tensor, the step below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, precision):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _q8(x), _q8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, pos, c):
    """Rotary on the first half of each head; that half's two quarters
    rotate against each other. x (L, h, hd), pos (L,)."""
    hd = c["head_dim"]
    rot, keep = x[..., :hd // 2], x[..., hd // 2:]
    q = hd // 4
    freqs = 1.0 / (c["rope_theta"] ** (jnp.arange(q, dtype=jnp.float32) / q))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = rot[..., :q], rot[..., q:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, keep],
                           axis=-1)


def _attend(q, k, v, c):
    """Causal grouped-query attention of one row. q (L, h, hd), k/v
    (L, kv, hd)."""
    L, h, hd = q.shape
    g = h // c["n_kv_heads"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for q0 in range(0, L, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        s = s / math.sqrt(hd)
        qpos = q0 + jnp.arange(qb.shape[0])[:, None]
        s = jnp.where(jnp.arange(L)[None, :] <= qpos, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _layer(x, layers, k, l, c, precision):
    c = dict(c)
    p = jax.tree.map(lambda a: a[k, l], layers)
    h, kv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    pos = jnp.arange(x.shape[1])

    def row(xr):
        a = _rms(xr, p["ln1"], c["norm_eps"])
        q = _mm(a, p["attn"]["wq"], precision).reshape(-1, h, hd)
        kk = _mm(a, p["attn"]["wk"], precision).reshape(-1, kv, hd)
        vv = _mm(a, p["attn"]["wv"], precision).reshape(-1, kv, hd)
        q, kk = _rope(q, pos, c), _rope(kk, pos, c)
        o = _attend(q, kk, vv, c).reshape(-1, h * hd)
        xr = xr + _mm(o, p["attn"]["wo"], precision)
        a = _rms(xr, p["ln2"], c["norm_eps"])
        gate = _mm(a, p["mlp"]["w_gate"], precision)
        up = _mm(a, p["mlp"]["w_up"], precision)
        return xr + _mm(jax.nn.silu(gate) * up, p["mlp"]["w_down"], precision)

    return jax.lax.map(row, x)


@functools.partial(jax.jit, static_argnames=("c",))
def _embed(tokens, tok_table, k, c):
    return jnp.take(tok_table[k], tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _head(x_sel, final_norm, head, k, c, precision):
    c = dict(c)
    a = _rms(x_sel, final_norm[k], c["norm_eps"])
    return _mm(a, head[k][:, :c["vocab_size"]], precision)


def logits(c: dict, params, tokens, rows, cols, k: int = 0,
           precision: str = "fp32"):
    """Logits (n, vocab) at positions ``cols`` of rows ``rows`` of
    ``tokens`` (B, L) int32: the logits that predict the token after each
    such position."""
    ck = tuple(sorted((a, b) for a, b in c.items()
                      if isinstance(b, (int, float, str, bool))))
    x = _embed(jnp.asarray(tokens), params["embed"]["tok"], k, ck)
    for l in range(c["n_layers"]):
        x = _layer(x, params["layers"], k, l, ck, precision)
    x_sel = x[jnp.asarray(rows), jnp.asarray(cols)]
    return _head(x_sel, params["final_norm"], params["head"], k, ck,
                 precision)
