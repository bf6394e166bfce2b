"""Optimizers (pure JAX, no optax dependency).

AdamW keeps fp32 first/second moments regardless of the parameter dtype
(mixed-precision training: bf16 params, fp32 state).  The moment pytrees
mirror the parameter pytree, so parameter PartitionSpecs apply verbatim
(ZeRO-style state sharding comes for free from the FSDP param specs).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Union

import jax
import jax.numpy as jnp
import numpy as np


def cosine_schedule(base_lr: float, warmup: int, total: int) -> Callable:
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * step / max(warmup, 1)
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = 0.5 * base_lr * (1.0 + jnp.cos(np.pi * frac))
        return jnp.where(step < warmup, warm, cos)
    return lr


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: Union[float, Callable] = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0

    def init(self, params) -> Any:
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"m": jax.tree.map(zeros, params),
                "v": jax.tree.map(zeros, params),
                "step": jnp.zeros((), jnp.int32)}

    def apply(self, params, grads, state):
        """One update (scope ``optimizer``)."""
        with jax.named_scope("optimizer"):
            return self._apply(params, grads, state)

    def _apply(self, params, grads, state):
        step = state["step"] + 1
        lr = self.lr(step) if callable(self.lr) else self.lr

        if self.clip_norm:
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grads)))
            scale = jnp.minimum(1.0, self.clip_norm / (gnorm + 1e-9))
            grads = jax.tree.map(
                lambda g: (g.astype(jnp.float32) * scale), grads)
        else:
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        b1, b2 = self.b1, self.b2
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g,
                         state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g),
                         state["v"], grads)
        t = step.astype(jnp.float32)
        mc = 1 - b1 ** t
        vc = 1 - b2 ** t

        def upd(p, m_, v_):
            u = (m_ / mc) / (jnp.sqrt(v_ / vc) + self.eps)
            if self.weight_decay and p.ndim >= 2:  # no decay on norms/bias
                u = u + self.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

        new_params = jax.tree.map(upd, params, m, v)
        return new_params, {"m": m, "v": v, "step": step}


@dataclasses.dataclass(frozen=True)
class Sgd:
    lr: Union[float, Callable] = 1e-2
    momentum: float = 0.0

    def init(self, params):
        if not self.momentum:
            return {"step": jnp.zeros((), jnp.int32)}
        return {"mu": jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "step": jnp.zeros((), jnp.int32)}

    def apply(self, params, grads, state):
        """One update (scope ``optimizer``)."""
        with jax.named_scope("optimizer"):
            return self._apply(params, grads, state)

    def _apply(self, params, grads, state):
        step = state["step"] + 1
        lr = self.lr(step) if callable(self.lr) else self.lr
        if self.momentum:
            mu = jax.tree.map(
                lambda m, g: self.momentum * m + g.astype(jnp.float32),
                state["mu"], grads)
            params = jax.tree.map(
                lambda p, m: (p.astype(jnp.float32) - lr * m).astype(p.dtype),
                params, mu)
            return params, {"mu": mu, "step": step}
        params = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return params, {"step": step}
