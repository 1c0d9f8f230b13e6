"""Optimizers from scratch (no optax): AdamW, SGD-momentum, schedules.

State layout is a plain pytree (m, v, count) so checkpointing and sharding
treat it like any other tree; fp32 moments regardless of param dtype.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding

f32 = jnp.float32


class AdamWState(NamedTuple):
    m: any
    v: any
    count: jax.Array


def adamw_init(params) -> AdamWState:
    """Zero moments, each with its parameter's sharding (``zeros_like``
    keeps a committed array's), and a step count replicated over the bound
    mesh, if one is bound (``repro.sharding``)."""
    zeros = lambda p: jnp.zeros_like(p, dtype=f32)
    return AdamWState(jax.tree.map(zeros, params),
                      jax.tree.map(zeros, params),
                      jnp.zeros((), jnp.int32, device=sharding.named_sharding(())))


def adamw_update(grads, state: AdamWState, params, *, lr, b1=0.9, b2=0.95,
                 eps=1e-8, weight_decay=0.01):
    c = state.count + 1
    bc1 = 1 - b1 ** c.astype(f32)
    bc2 = 1 - b2 ** c.astype(f32)

    def upd(g, m, v, p):
        gf = g.astype(f32)
        m2 = b1 * m + (1 - b1) * gf
        v2 = b2 * v + (1 - b2) * gf * gf
        step = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
        step = step + weight_decay * p.astype(f32)
        return (p.astype(f32) - lr * step).astype(p.dtype), m2, v2

    out = jax.tree.map(upd, grads, state.m, state.v, params)
    new_p = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple))
    return new_p, AdamWState(new_m, new_v, c)


class SGDState(NamedTuple):
    mom: any
    count: jax.Array


def sgd_init(params) -> SGDState:
    return SGDState(jax.tree.map(lambda p: jnp.zeros(p.shape, f32), params),
                    jnp.zeros((), jnp.int32))


def sgd_update(grads, state: SGDState, params, *, lr, momentum=0.9):
    def upd(g, m, p):
        m2 = momentum * m + g.astype(f32)
        return (p.astype(f32) - lr * m2).astype(p.dtype), m2
    out = jax.tree.map(upd, grads, state.mom, params)
    new_p = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return new_p, SGDState(new_m, state.count + 1)


def global_norm(tree) -> jax.Array:
    sq = sum(jnp.sum(jnp.square(l.astype(f32))) for l in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def clip_by_global_norm(grads, max_norm: float):
    n = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(n, 1e-9))
    return jax.tree.map(lambda g: (g.astype(f32) * scale).astype(g.dtype),
                        grads), n


def cosine_schedule(base_lr: float, warmup: int, total: int
                    ) -> Callable[[jax.Array], jax.Array]:
    def lr(step):
        s = step.astype(f32)
        warm = base_lr * s / max(warmup, 1)
        prog = jnp.clip((s - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = base_lr * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return jnp.where(s < warmup, warm, cos)
    return lr
