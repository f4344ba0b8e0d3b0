"""AdamW with per-trial (vmapped) hyperparameters — pure JAX, no optax.

Hydra trains K trials in one SPMD program, so every hyperparameter that the
model-selection layer searches over (learning rate, weight decay, β1/β2) is a
(K,) array broadcast against the leading trial axis of each parameter leaf.
Optimizer state mirrors the parameter sharding exactly (ZeRO-1 falls out of
FSDP param sharding for free: sharded param shard ⇒ sharded m/v shard).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp


def _bcast(vec, leaf):
    """(K,) -> (K, 1, 1, ...) matching leaf rank."""
    return vec.reshape(vec.shape + (1,) * (leaf.ndim - 1))


# ---------------------------------------------------------------------------
# LR schedules
# ---------------------------------------------------------------------------


def constant_schedule(step):
    return jnp.ones_like(step, jnp.float32)


def warmup_cosine_schedule(warmup: int, total: int, final_frac: float = 0.1):
    def fn(step):
        step = step.astype(jnp.float32)
        warm = jnp.minimum(step / jnp.maximum(warmup, 1), 1.0)
        prog = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1), 0, 1)
        cos = final_frac + (1 - final_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return warm * cos
    return fn


def warmup_linear_schedule(warmup: int, total: int):
    def fn(step):
        step = step.astype(jnp.float32)
        warm = jnp.minimum(step / jnp.maximum(warmup, 1), 1.0)
        prog = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1), 0, 1)
        return warm * (1 - prog)
    return fn


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AdamW:
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0  # default; override per-trial via hparams["wd"]
    grad_clip: float = 0.0  # 0 = off; per-trial clip-by-global-norm
    schedule: Callable = dataclasses.field(default=constant_schedule)

    def init(self, params):
        # zeros_like keeps each leaf's sharding: the moments land on the
        # devices that hold the params, not all on the default device
        zeros = jax.tree.map(
            lambda p: jnp.zeros_like(p, jnp.float32), params)
        return {"m": zeros,
                "v": jax.tree.map(jnp.zeros_like, zeros),
                "count": jnp.zeros((), jnp.int32)}

    def init_struct(self, params_struct):
        """ShapeDtypeStruct view of ``init`` (dry-run)."""
        z = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
            params_struct)
        return {"m": z, "v": z,
                "count": jax.ShapeDtypeStruct((), jnp.int32)}

    def state_pspecs(self, pspecs):
        from jax.sharding import PartitionSpec as P
        return {"m": pspecs, "v": pspecs, "count": P()}

    def update(self, params, grads, state, hparams, step,
               grad_norm: Optional[jnp.ndarray] = None):
        """One AdamW step. hparams: {"lr": (K,), optional "wd": (K,)}.

        ``grad_norm`` is the per-trial global gradient norm (K,), computed
        sharding-aware by the caller; used for clip-by-global-norm.
        """
        lr = hparams["lr"].astype(jnp.float32) * self.schedule(step)
        wd = hparams.get("wd")
        if wd is None:
            wd = jnp.full_like(lr, self.weight_decay)
        count = state["count"] + 1
        b1c = 1 - self.b1 ** count.astype(jnp.float32)
        b2c = 1 - self.b2 ** count.astype(jnp.float32)

        if self.grad_clip > 0 and grad_norm is not None:
            scale = jnp.minimum(1.0, self.grad_clip / (grad_norm + 1e-9))
        else:
            scale = jnp.ones_like(lr)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32) * _bcast(scale, g)
            m_new = self.b1 * m + (1 - self.b1) * g
            v_new = self.b2 * v + (1 - self.b2) * jnp.square(g)
            mhat = m_new / b1c
            vhat = v_new / b2c
            delta = mhat / (jnp.sqrt(vhat) + self.eps) \
                + _bcast(wd, p) * p.astype(jnp.float32)
            p_new = p.astype(jnp.float32) - _bcast(lr, p) * delta
            return p_new.astype(p.dtype), m_new, v_new

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        out = [upd(p, g, m, v)
               for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        params_new = jax.tree.unflatten(treedef, [o[0] for o in out])
        m_new = jax.tree.unflatten(treedef, [o[1] for o in out])
        v_new = jax.tree.unflatten(treedef, [o[2] for o in out])
        return params_new, {"m": m_new, "v": v_new, "count": count}


# ---------------------------------------------------------------------------
# SGD (for the paper's MLP accuracy-parity experiment: plain, no state)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SGD:
    momentum: float = 0.0

    def init(self, params):
        if self.momentum == 0.0:
            return {"count": jnp.zeros((), jnp.int32)}
        return {"mom": jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "count": jnp.zeros((), jnp.int32)}

    def state_pspecs(self, pspecs):
        from jax.sharding import PartitionSpec as P
        if self.momentum == 0.0:
            return {"count": P()}
        return {"mom": pspecs, "count": P()}

    def update(self, params, grads, state, hparams, step, grad_norm=None):
        lr = hparams["lr"].astype(jnp.float32)
        count = state["count"] + 1
        if self.momentum == 0.0:
            new = jax.tree.map(
                lambda p, g: (p.astype(jnp.float32)
                              - _bcast(lr, p) * g.astype(jnp.float32)
                              ).astype(p.dtype), params, grads)
            return new, {"count": count}
        mom = jax.tree.map(
            lambda mo, g: self.momentum * mo + g.astype(jnp.float32),
            state["mom"], grads)
        new = jax.tree.map(
            lambda p, mo: (p.astype(jnp.float32) - _bcast(lr, p) * mo
                           ).astype(p.dtype), params, mom)
        return new, {"mom": mom, "count": count}
