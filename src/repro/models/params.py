"""Structural parameter definitions.

Models declare their parameters as a pytree of ``ParamDef`` (shape + logical
sharding axes + initializer). The same tree serves three consumers:

  * ``materialize``  — real initialization for training / smoke tests,
  * ``abstract``     — ShapeDtypeStructs for the dry-run (no allocation),
  * ``logical_axes`` — per-leaf logical axes for in_shardings resolution.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]    # logical axes, len == len(shape)
    init: str = "normal"               # normal | zeros | ones | scaled
    dtype: str = "bfloat16"
    scale: float = 1.0                 # stddev multiplier for normal/scaled

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


STACK_AXES = ("layer", "expert")


def fan_in(d: ParamDef) -> int:
    """The contracted size of a weight: every axis but the last, less the
    stacking axes (layers, experts); a vector's own length."""
    if len(d.shape) == 1:
        return max(1, d.shape[0])
    return max(1, int(np.prod([n for n, ax in zip(d.shape[:-1], d.axes[:-1])
                               if ax not in STACK_AXES])))


def materialize(defs, key: jax.Array):
    """Initialize real parameters on the default device."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=_is_def)
    keys = jax.random.split(key, len(leaves))
    out = []
    for d, k in zip(leaves, keys):
        dt = jnp.dtype(d.dtype)
        if d.init == "zeros":
            out.append(jnp.zeros(d.shape, dt))
        elif d.init == "ones":
            out.append(jnp.ones(d.shape, dt))
        elif d.init == "arange_neg":   # mamba A_log init: log(1..n)
            out.append(jnp.log(jnp.arange(1, d.shape[-1] + 1, dtype=jnp.float32)
                               ).astype(dt) * jnp.ones(d.shape, dt))
        else:
            std = (d.scale / np.sqrt(fan_in(d)) if d.init == "scaled"
                   else 0.02 * d.scale)
            out.append((jax.random.normal(k, d.shape, jnp.float32) * std).astype(dt))
    return jax.tree.unflatten(treedef, out)


def abstract(defs):
    """ShapeDtypeStruct tree — used by .lower() in the dry-run."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype)),
        defs, is_leaf=_is_def)


def logical_axes(defs):
    return jax.tree.map(lambda d: d.axes, defs, is_leaf=_is_def)


def count(defs) -> int:
    return sum(int(np.prod(d.shape)) for d in jax.tree.leaves(defs, is_leaf=_is_def))
