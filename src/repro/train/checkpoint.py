"""Fault-tolerant checkpointing: atomic, mesh-independent, resumable.

Layout:  <dir>/step_<N>/arrays.npz  + manifest.json
Commit protocol: write into ``step_<N>.tmp`` then ``os.replace`` — a crash
mid-write can never produce a half-checkpoint that restore would pick up.
Arrays are saved as host numpy per logical tensor (gathered if sharded), so
restore works on a DIFFERENT mesh/pod count: the launcher re-shards on load
(elastic restart, DESIGN.md §7).
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

_SEP = "\x1f"          # flat-key separator (never appears in field names)


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(_key_str(p) for p in path)
        flat[key] = np.asarray(jax.device_get(leaf))
    return flat


def _key_str(p) -> str:
    if hasattr(p, "key"):
        return f"k:{p.key}"
    if hasattr(p, "idx"):
        return f"i:{p.idx}"
    if hasattr(p, "name"):
        return f"k:{p.name}"
    return f"r:{p}"


def save(ckpt_dir: str, step: int, tree, extra: Optional[Dict] = None,
         keep: int = 3) -> str:
    """Atomically write checkpoint for ``step``; prune to ``keep`` newest."""
    final = os.path.join(ckpt_dir, f"step_{step:010d}")
    tmp = final + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    np.savez(os.path.join(tmp, "arrays.npz"), **_flatten(tree))
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump({"step": step, "extra": extra or {}}, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)                      # atomic commit
    _prune(ckpt_dir, keep)
    return final


def save_async(ckpt_dir: str, step: int, tree, extra=None, keep: int = 3
               ) -> threading.Thread:
    """Checkpoint on a writer thread: device_get happens eagerly (snapshot),
    serialization overlaps the next training steps."""
    flat = _flatten(tree)                       # snapshot before returning

    def _write():
        final = os.path.join(ckpt_dir, f"step_{step:010d}")
        tmp = final + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        np.savez(os.path.join(tmp, "arrays.npz"), **flat)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump({"step": step, "extra": extra or {}}, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)
        _prune(ckpt_dir, keep)

    t = threading.Thread(target=_write, daemon=True)
    t.start()
    return t


def _prune(ckpt_dir: str, keep: int):
    steps = sorted(_list_steps(ckpt_dir))
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:010d}"),
                      ignore_errors=True)


def _list_steps(ckpt_dir: str):
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for name in os.listdir(ckpt_dir):
        m = re.fullmatch(r"step_(\d+)", name)
        if m:
            out.append(int(m.group(1)))
    return out


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = _list_steps(ckpt_dir)
    return max(steps) if steps else None


def restore(ckpt_dir: str, tree_like, step: Optional[int] = None,
            shardings=None) -> Tuple[Any, int, Dict]:
    """Restore into the structure of ``tree_like``; optionally re-shard with
    a pytree of NamedShardings (elastic restart on a new mesh)."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:010d}")
    data = np.load(os.path.join(d, "arrays.npz"))
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)

    paths = jax.tree_util.tree_flatten_with_path(tree_like)[0]
    treedef = jax.tree_util.tree_structure(tree_like)
    shard_leaves = (jax.tree.leaves(shardings) if shardings is not None
                    else [None] * len(paths))
    leaves = []
    for (path, like), sh in zip(paths, shard_leaves):
        key = _SEP.join(_key_str(p) for p in path)
        arr = data[key]
        if arr.dtype.kind == "V":       # npz keeps bfloat16 as raw 2-byte void
            arr = arr.view(like.dtype)
        if sh is not None:
            leaves.append(jax.device_put(arr, sh))
        else:
            leaves.append(jax.numpy.asarray(arr, dtype=like.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves), step, manifest["extra"]
