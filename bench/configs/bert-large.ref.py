"""Plain reference of the BERT-Large training step as the benchmark runs it:
jax.numpy, float32 at JAX's default matmul precision (the precision the
configuration states), one trial at a time, no pipeline, no sharding. It
imports nothing of the program: it reads the sizes and the optimizer from
``bert-large.json`` and the weights by their names in the parameter tree
(``embed/tok``, ``layers/attn/wq``, ...).

The block is the program's: pre-norm layer norms, non-causal attention,
learned positions, tanh-GELU feed-forward, a final layer norm and an
untied head, next-token cross-entropy averaged over every token. The
optimizer is AdamW with a per-trial learning rate and weight decay and
clipping by the trial's global gradient norm.

``precision="bf16"`` is the control, one step below float32: the forward
and backward passes in bfloat16 (operands, activations and the residual
stream; products accumulate in float32), master weights and optimizer
state in float32. ``keep`` (a fraction) is a planted fault: the loss
averages only the first ``keep`` of the microbatches.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _ln(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def _mm(x, w, dt):
    out = jnp.matmul(x.astype(dt), w.astype(dt),
                     preferred_element_type=jnp.float32)
    return out.astype(dt)


def _layer(c, dt, p, x):
    h, kv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    b, s, _ = x.shape
    a = _ln(x, p["ln1_w"], p["ln1_b"], c["norm_eps"])
    q = _mm(a, p["attn"]["wq"], dt).reshape(b, s, h, hd)
    k = jnp.repeat(_mm(a, p["attn"]["wk"], dt).reshape(b, s, kv, hd),
                   h // kv, axis=2)
    v = jnp.repeat(_mm(a, p["attn"]["wv"], dt).reshape(b, s, kv, hd),
                   h // kv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=jnp.float32) / math.sqrt(hd)
    pr = jax.nn.softmax(sc, axis=-1).astype(dt)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v,
                   preferred_element_type=jnp.float32).astype(dt)
    x = x + _mm(o.reshape(b, s, h * hd), p["attn"]["wo"], dt)
    a = _ln(x, p["ln2_w"], p["ln2_b"], c["norm_eps"])
    u = jax.nn.gelu(_mm(a, p["mlp"]["w_up"], dt).astype(jnp.float32))
    return x + _mm(u, p["mlp"]["w_down"], dt)


def _loss(c, dt, p, tokens, labels):
    """Mean next-token cross-entropy over (rows, seq) of one trial."""
    s = tokens.shape[-1]
    x = jnp.take(p["embed"]["tok"], tokens, axis=0) + p["embed"]["pos"][:s][None]
    x = x.astype(dt)
    layer = jax.checkpoint(functools.partial(_layer, c, dt))
    for l in range(c["n_layers"]):
        x = layer(jax.tree.map(lambda a: a[l], p["layers"]), x)
    x = _ln(x, p["final_norm"]["w"], p["final_norm"]["b"], c["norm_eps"])
    logits = jnp.matmul(x.astype(dt),
                        p["head"][:, :c["vocab_size"]].astype(dt),
                        preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _grad(p, tokens, labels, c, precision):
    dt = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[precision]
    return jax.value_and_grad(functools.partial(_loss, dict(c), dt))(
        p, tokens, labels)


@functools.partial(jax.jit, static_argnames=("c",), donate_argnums=(0, 1))
def _adamw(p, st, g, lr, wd, t, c):
    o = dict(dict(c)["optimizer"])
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, o["grad_clip"] / (gn + 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    m = jax.tree.map(lambda m, x: o["b1"] * m + (1 - o["b1"]) * x, st["m"], g)
    v = jax.tree.map(lambda v, x: o["b2"] * v + (1 - o["b2"]) * x * x,
                     st["v"], g)
    b1c, b2c = 1 - o["b1"] ** t, 1 - o["b2"] ** t
    p = jax.tree.map(lambda p, m, v: p - lr * (
        (m / b1c) / (jnp.sqrt(v / b2c) + o["eps"]) + wd * p), p, m, v)
    return p, {"m": m, "v": v}, g


def _freeze(c):
    def f(v):
        return tuple(sorted((a, f(b)) for a, b in v.items())) \
            if isinstance(v, dict) else v
    return tuple(sorted((a, f(b)) for a, b in c.items()
                        if isinstance(b, (int, float, str, bool, dict))))


def train(c: dict, p: dict, batches: list, lr: float, wd: float,
          precision: str = "fp32", keep: float = 1.0) -> dict:
    """Steps of one trial from weights ``p`` (this trial's float32 tree) on
    ``batches`` (each {"tokens", "labels"}: (M, mb, seq)). Returns the loss
    of each step, the first step's clipped gradient and the last weights."""
    ck = _freeze(c)
    st = {"m": jax.tree.map(jnp.zeros_like, p),
          "v": jax.tree.map(jnp.zeros_like, p)}
    losses, g1 = [], None
    for t, bt in enumerate(batches, start=1):
        m = bt["tokens"].shape[0]
        n = max(1, int(round(m * keep)))
        tok = jnp.asarray(bt["tokens"][:n]).reshape(-1, bt["tokens"].shape[-1])
        lab = jnp.asarray(bt["labels"][:n]).reshape(tok.shape)
        loss, g = _grad(p, tok, lab, ck, precision)
        losses.append(float(loss))
        p, st, g = _adamw(p, st, g, jnp.float32(lr), jnp.float32(wd),
                          jnp.float32(t), ck)
        if g1 is None:
            g1 = g
        del g
    return {"losses": losses, "grad1": g1, "params": p}
