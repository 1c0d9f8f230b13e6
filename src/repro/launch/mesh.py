"""Production mesh construction + per-(arch x shape) input specs.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). The mesh is built from the LIVE device set — elastic
restarts on a different pod count re-mesh here and re-shard from the
mesh-independent checkpoints (train/checkpoint.py).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs import SHAPES, get_config
from repro.configs.base import ModelConfig
from repro.models import lm
from repro.models.params import ParamDef, abstract, logical_axes


def _auto_mesh(shape, axes, devices=None):
    """Mesh whose axes are ``Auto``: the logical-axis rules in
    ``repro.sharding`` place arrays with sharding constraints, which JAX
    accepts only on ``Auto`` axes (``jax.make_mesh`` defaults to
    ``Explicit``)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_from_devices(devices=None, model_parallel: int = 16):
    """Elastic variant: mesh over whatever devices are alive."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    mp = math.gcd(model_parallel, n)
    return _auto_mesh((n // mp, mp), ("data", "model"), devices=devices)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape_name: str) -> Dict:
    """Model inputs for one assigned shape, as ShapeDtypeStructs.

    train/prefill: token batch (+ labels for train, + modality stubs);
    decode: one new token + positions (the KV cache is separate state,
    see ``state_specs``).
    """
    seq, batch, kind = SHAPES[shape_name]
    i32 = jnp.int32
    if kind == "train":
        d = {"tokens": jax.ShapeDtypeStruct((batch, seq), i32),
             "labels": jax.ShapeDtypeStruct((batch, seq), i32)}
    elif kind == "prefill":
        d = {"tokens": jax.ShapeDtypeStruct((batch, seq), i32)}
    else:                                     # decode: one token per row
        return {"tokens": jax.ShapeDtypeStruct((batch, 1), i32),
                "position": jax.ShapeDtypeStruct((batch,), i32)}
    if cfg.num_image_tokens:
        d["image_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.encoder_layers:
        d["encoder_frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.num_encoder_frames, cfg.d_model), jnp.bfloat16)
    return d


def input_axes(cfg: ModelConfig, shape_name: str) -> Dict:
    """Logical axes for every input (resolved against mesh rules)."""
    _, _, kind = SHAPES[shape_name]
    if kind == "decode":
        return {"tokens": ("batch", None), "position": ("batch",)}
    d = {"tokens": ("batch", "seq"), "labels": ("batch", "seq")}
    if kind == "prefill":
        d.pop("labels")
    if cfg.num_image_tokens:
        d["image_embeds"] = ("batch", None, "embed")
    if cfg.encoder_layers:
        d["encoder_frames"] = ("batch", None, "embed")
    return d


def decode_state_specs(cfg: ModelConfig, shape_name: str):
    """(abstract cache, cache logical axes) for decode shapes."""
    seq, batch, kind = SHAPES[shape_name]
    assert kind == "decode"
    defs = lm.cache_defs(cfg, batch, seq)
    return abstract(defs), logical_axes(defs)


def shape_rules(cfg: ModelConfig, shape_name: str) -> Optional[Dict]:
    """Per-shape sharding-rule overrides.

    long_500k has global_batch=1: batch axes are useless, so the KV cache /
    SSD state shard their LONG axes over the data(+pod) axes instead.
    Decode with kv_heads not divisible by the 16-way model axis switches the
    cache to sequence-parallel (kv_seq over 'model') — the head partition is
    dropped by fix_divisibility.
    """
    if shape_name == "long_500k":
        return {"batch": None, "kv_seq": ("pod", "data"),
                "heads": ("model",), "seq": None}
    _, _, kind = SHAPES[shape_name]
    if kind == "decode" and cfg.num_kv_heads and cfg.num_kv_heads % 16 != 0:
        return {"kv_seq": "model"}
    return None


def model_flops(cfg: ModelConfig, shape_name: str) -> float:
    """Analytic useful FLOPs per step: 6·N·D train, 2·N·D fwd-only
    (N = active params for MoE)."""
    seq, batch, kind = SHAPES[shape_name]
    n = cfg.active_param_count()
    if kind == "train":
        return 6.0 * n * batch * seq
    if kind == "prefill":
        return 2.0 * n * batch * seq
    return 2.0 * n * batch                   # decode: one token per row
