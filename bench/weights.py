"""The benchmark's own weights: made on the device from ``--seed`` in one
jitted call, in the program's parameter layout and sharding and in the
dtype they are served or trained in.

The program only receives them. The plain reference reads the same arrays
by their names, so nothing that the program made reaches the comparison.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from common import jax_key


def leaf_name(path) -> str:
    return "/".join(str(p.key) if hasattr(p, "key") else str(p)
                    for p in path)


def _leaf(name: str, s, key, vocab: int):
    """One leaf: embeddings ~ N(0, 1); matrices ~ N(0, 1/fan_in) with the
    fan-in on the second-last axis; norm weights 1 + N(0, 0.1^2); biases
    N(0, 0.02^2). Rows or columns of a vocabulary padded past ``vocab`` are
    zero, as the program pads them."""
    shape = s.shape
    rest = shape[2:] if name.startswith("layers/") else shape[1:]
    x = jax.random.normal(key, shape, jnp.float32)
    if name in ("embed/tok", "embed/pos"):
        pass
    elif len(rest) >= 2:
        x = x / math.sqrt(rest[-2])
    elif name.endswith("_b") or name.endswith("/b"):
        x = 0.02 * x
    else:
        x = 1.0 + 0.1 * x
    if name == "embed/tok" and shape[1] > vocab:
        x = jnp.where(jnp.arange(shape[1])[None, :, None] < vocab, x, 0.0)
    if name == "head" and shape[2] > vocab:
        x = jnp.where(jnp.arange(shape[2])[None, None, :] < vocab, x, 0.0)
    return x.astype(s.dtype)


def make(struct, shardings, seed: int, vocab: int, stream: int = 1):
    """Weights shaped like ``struct`` (a pytree of ShapeDtypeStruct), placed
    as ``shardings`` says, from the seed."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(struct)

    def init(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(leaf_name(p), s, jax.random.fold_in(key, i), vocab)
            for i, (p, s) in enumerate(flat)])

    return jax.jit(init, out_shardings=shardings)(jax_key(jax, seed, stream))


def like(params, seed: int, vocab: int, stream: int = 1):
    """New weights with the shapes, dtypes and shardings of ``params``."""
    struct = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          params)
    shard = jax.tree.map(lambda a: a.sharding, params)
    return make(struct, shard, seed, vocab, stream)
