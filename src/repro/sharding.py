"""Logical-axis sharding: declarative rules resolved against the live mesh.

Models annotate tensors with *logical* axes ("batch", "heads", "mlp", ...);
the launcher binds a mesh + a rule table, and every annotation resolves to a
``PartitionSpec``. Outside a bound mesh the annotations are no-ops, so unit
tests and the DSE plane never touch device state.

Rules follow MaxText conventions:
  fsdp-style weight sharding over the ("pod","data") axes, tensor parallelism
  over "model", expert parallelism over "model" when divisible, sequence
  sharding of long KV caches over "data".
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Union[None, str, Tuple[str, ...]]

# logical axis -> mesh axis (or tuple of mesh axes, or None=replicated)
DEFAULT_RULES: Dict[str, Axes] = {
    "batch": ("pod", "data"),       # data parallel over pod x data
    "seq": None,                    # sequence replicated by default
    "kv_seq": "data",               # long-context decode: shard cache sequence
    "embed": None,                  # activations' feature dim replicated
    "fsdp": ("pod", "data"),        # weight matrices' input dim (ZeRO-3 style)
    "tensor": "model",              # Megatron column/row parallel dim
    "heads": "model",
    "kv_heads": "model",
    "vocab": "model",
    # Experts replicated across the mesh by default: each expert's (D,F)
    # weight is already 512-way sharded via fsdp x tensor, and 8 experts on a
    # 16-way axis would pad 2x. Expert parallelism (expert -> "model") is a
    # per-run rule override (see EXPERIMENTS.md §Perf hillclimb: jamba/grok).
    "expert": None,
    # MoE dispatch buffers (E, C, D): shard the CAPACITY dim over the batch
    # axes. Leaving it unsharded replicates the whole dispatch buffer and
    # all-reduces it in the backward pass — measured 2x86 GB/device/step on
    # mixtral train_4k (§Perf cell B, iteration B1).
    "expert_cap": ("pod", "data"),
    "layer": None,                  # stacked-layer leading dim
    "conv": None,
}

_TLS = threading.local()


def _ctx() -> Optional[Tuple[Mesh, Dict[str, Axes]]]:
    return getattr(_TLS, "ctx", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[Dict[str, Axes]] = None):
    """Bind a mesh + rules; inside, logical annotations become constraints."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    # Drop rules that reference axes the mesh does not have (single-pod mesh
    # has no "pod" axis).
    names = set(mesh.axis_names)

    def _filter(a: Axes) -> Axes:
        if a is None:
            return None
        if isinstance(a, str):
            return a if a in names else None
        kept = tuple(x for x in a if x in names)
        return kept if kept else None

    merged = {k: _filter(v) for k, v in merged.items()}
    prev = _ctx()
    _TLS.ctx = (mesh, merged)
    try:
        with mesh:
            yield
    finally:
        _TLS.ctx = prev


def resolve_spec(logical: Sequence[Optional[str]]) -> P:
    ctx = _ctx()
    if ctx is None:
        return P(*([None] * len(logical)))
    _, rules = ctx
    out, used = [], set()
    for ax in logical:
        m = rules.get(ax) if ax else None
        # one mesh axis may appear only once in a spec
        if m is None:
            out.append(None)
        elif isinstance(m, str):
            out.append(None if m in used else m)
            used.add(m)
        else:
            kept = tuple(x for x in m if x not in used)
            used.update(kept)
            # a 1-tuple means the same sharding as the bare axis name, but
            # newer jax PartitionSpec no longer compares them equal
            out.append(kept[0] if len(kept) == 1 else (kept if kept else None))
    return P(*out)


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Annotate activation ``x`` with logical axes (no-op without a mesh)."""
    ctx = _ctx()
    if ctx is None:
        return x
    mesh, _ = ctx
    spec = resolve_spec(logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(logical: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    ctx = _ctx()
    if ctx is None:
        return None
    mesh, _ = ctx
    return NamedSharding(mesh, resolve_spec(logical))


def _named_tree(axes_tree):
    return jax.tree.map(
        named_sharding, axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


def spec_tree(axes_tree, mesh: Mesh, rules: Optional[Dict[str, Axes]] = None):
    """Resolve a pytree of logical-axis tuples into NamedShardings."""
    with use_mesh(mesh, rules):
        return _named_tree(axes_tree)


def constrain(tree, axes_tree):
    """Pin each array of ``tree`` to the sharding its logical axes
    (``axes_tree``) resolve to under the bound mesh and rules, less the mesh
    axes that do not divide its dimensions (``fix_divisibility``). No-op
    without a mesh."""
    if _ctx() is None:
        return tree
    shardings = fix_divisibility(_named_tree(axes_tree), tree)
    return jax.tree.map(jax.lax.with_sharding_constraint, tree, shardings)


def fix_divisibility(shardings, abstract_tree):
    """Drop partitioned mesh axes that do not divide the tensor dimension.

    ``jax.jit`` in_shardings require exact divisibility (unlike
    with_sharding_constraint, which pads). E.g. an 8-kv-head cache cannot
    take a 16-way 'model' partition on its head dim — the axis is dropped
    (the launcher compensates with a sequence-parallel rule; DESIGN.md §7).
    """
    def fix(sh: Optional[NamedSharding], ab):
        if sh is None:
            return None
        spec, shape = sh.spec, ab.shape
        out = []
        for d, part in enumerate(spec):
            if part is None or d >= len(shape):
                out.append(part)
                continue
            axes = (part,) if isinstance(part, str) else tuple(part)
            kept = []
            size = 1
            for a in axes:
                n = sh.mesh.shape[a]
                if shape[d] % (size * n) == 0:
                    kept.append(a)
                    size *= n
            out.append(tuple(kept) if len(kept) > 1
                       else (kept[0] if kept else None))
        return NamedSharding(sh.mesh, P(*out))

    return jax.tree.map(fix, shardings, abstract_tree,
                        is_leaf=lambda x: x is None or isinstance(
                            x, NamedSharding))
