"""MoonViT vision tower, 2x2 patch merger and MLP projector (Kimi-VL,
arXiv:2504.07491), for square frames of a fixed size.

* Patches of ``vision_patch`` pixels a side, flattened (row, column,
  channel) and embedded by one matrix with a bias (the patch convolution).
* A learned ``vision_pos_grid`` x ``vision_pos_grid`` position table,
  resized bicubically to the patch grid (PyTorch's ``interpolate``:
  a = -0.75, half-pixel centres, edges clamped) and added.
* Pre-LayerNorm blocks: attention over the frame's patches with a fused qkv
  projection (with bias), 2D RoPE on q and k, an output projection (with
  bias); then a GELU (tanh) MLP. A final LayerNorm.
* 2D RoPE: of a head's ``hd / 2`` rotation pairs (consecutive elements), pair
  ``2j`` turns with the patch's column and pair ``2j + 1`` with its row, both
  at frequency ``theta ** (-4j / hd)``.
* The merger takes each ``vision_merge`` x ``vision_merge`` square of
  patches, in (row, column) order, LayerNorms each patch and concatenates
  them; the projector is Linear, GELU (erf), Linear to the LM's width.

Frames arrive normalised, (B, H, W, 3). Matmul inputs are in
``compute_dtype`` with float32 sums; norms and softmax are float32.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models.layers import blocked_attention, layernorm, mm
from repro.models.params import ParamDef

f32 = jnp.float32


def grid(cfg: ModelConfig) -> int:
    return cfg.image_hw // cfg.vision_patch


def param_defs(cfg: ModelConfig) -> Dict:
    Dv, F, P, L = (cfg.vision_d_model, cfg.vision_d_ff, cfg.vision_patch,
                   cfg.vision_layers)
    G = cfg.vision_pos_grid

    def ln(*lead):
        ax = ("layer",) * len(lead)
        return (ParamDef(lead + (Dv,), ax + ("embed",), "ones"),
                ParamDef(lead + (Dv,), ax + ("embed",), "zeros"))

    def dense(din, dout, *lead):
        ax = ("layer",) * len(lead)
        return (ParamDef(lead + (din, dout), ax + ("fsdp", "tensor"), "scaled"),
                ParamDef(lead + (dout,), ax + ("tensor",), "zeros"))

    blocks = {}
    for name, pair in (("ln0", ln(L)), ("qkv", dense(Dv, 3 * Dv, L)),
                       ("o", dense(Dv, Dv, L)), ("ln1", ln(L)),
                       ("fc0", dense(Dv, F, L)), ("fc1", dense(F, Dv, L))):
        blocks[f"{name}_w"], blocks[f"{name}_b"] = pair
    final_w, final_b = ln()
    patch_w, patch_b = dense(P * P * 3, Dv)
    return {
        "patch_w": patch_w, "patch_b": patch_b,
        "pos": ParamDef((G, G, Dv), (None, None, "embed"), "normal"),
        "blocks": blocks,
        "final_w": final_w, "final_b": final_b,
    }


def projector_defs(cfg: ModelConfig) -> Dict:
    Dv, D = cfg.vision_d_model, cfg.d_model
    Dm = Dv * cfg.vision_merge ** 2
    return {
        "ln_w": ParamDef((Dv,), ("embed",), "ones"),
        "ln_b": ParamDef((Dv,), ("embed",), "zeros"),
        "w1": ParamDef((Dm, Dm), ("fsdp", "tensor"), "scaled"),
        "b1": ParamDef((Dm,), ("tensor",), "zeros"),
        "w2": ParamDef((Dm, D), ("tensor", "fsdp"), "scaled"),
        "b2": ParamDef((D,), ("embed",), "zeros"),
    }


def bicubic_matrix(n_in: int, n_out: int) -> np.ndarray:
    """(n_out, n_in): PyTorch's bicubic resize along one axis
    (``align_corners=False``, a = -0.75, indices clamped at the edges)."""
    a = -0.75

    def cubic(t):
        t = abs(t)
        if t <= 1:
            return (a + 2) * t ** 3 - (a + 3) * t ** 2 + 1
        if t < 2:
            return a * t ** 3 - 5 * a * t ** 2 + 8 * a * t - 4 * a
        return 0.0

    m = np.zeros((n_out, n_in), np.float64)
    scale = n_in / n_out
    for i in range(n_out):
        src = scale * (i + 0.5) - 0.5
        i0 = int(np.floor(src))
        t = src - i0
        for k in range(-1, 3):
            m[i, min(max(i0 + k, 0), n_in - 1)] += cubic(t - k)
    return m


def position_table(cfg: ModelConfig, pos: jax.Array) -> jax.Array:
    """The learned table resized to the patch grid, (g*g, Dv)."""
    g = grid(cfg)
    r = jnp.asarray(bicubic_matrix(pos.shape[0], g), f32)
    out = jnp.einsum("ij,jkc,lk->ilc", r, pos.astype(f32), r,
                     precision=lax.Precision.HIGHEST)
    return out.reshape(g * g, -1)


def rope_2d_angles(cfg: ModelConfig) -> jax.Array:
    """(g*g, hd/2) rotation angles: even pairs by column, odd by row."""
    g, hd = grid(cfg), cfg.vision_d_model // cfg.vision_heads
    freqs = 1.0 / cfg.vision_rope_theta ** (np.arange(0, hd, 4)[: hd // 4] / hd)
    n = np.arange(g * g)
    col, row = n % g, n // g
    ang = np.stack([np.outer(col, freqs), np.outer(row, freqs)], axis=-1)
    return jnp.asarray(ang.reshape(g * g, hd // 2), f32)


def rope_2d(x: jax.Array, ang: jax.Array) -> jax.Array:
    """Rotate consecutive pairs of x (B, N, H, hd) by ang (N, hd/2)."""
    xr = x.astype(f32).reshape(*x.shape[:-1], -1, 2)
    a, b = xr[..., 0], xr[..., 1]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1
                     ).reshape(x.shape)


def _block(cfg: ModelConfig, x: jax.Array, p: Dict, ang: jax.Array) -> jax.Array:
    B, N, Dv = x.shape
    H = cfg.vision_heads
    hd = Dv // H
    cd = jnp.dtype(cfg.compute_dtype)
    eps = cfg.vision_norm_eps
    h = layernorm(x, p["ln0_w"], p["ln0_b"], eps)
    qkv = (mm(cfg, h, p["qkv_w"]) + p["qkv_b"]).reshape(B, N, 3, H, hd)
    q = rope_2d(qkv[:, :, 0], ang).astype(cd)
    k = rope_2d(qkv[:, :, 1], ang).astype(cd)
    att = blocked_attention(q, k, qkv[:, :, 2].astype(cd), hd ** -0.5,
                            False, 256)
    x = x + mm(cfg, att.reshape(B, N, Dv), p["o_w"]) + p["o_b"]
    h = layernorm(x, p["ln1_w"], p["ln1_b"], eps)
    h = jax.nn.gelu(mm(cfg, h, p["fc0_w"]) + p["fc0_b"], approximate=True)
    return x + mm(cfg, h, p["fc1_w"]) + p["fc1_b"]


def encode(cfg: ModelConfig, p: Dict, pixels: jax.Array) -> jax.Array:
    """Frames (B, H, W, 3) -> patch features (B, g*g, Dv), float32."""
    B = pixels.shape[0]
    P, g = cfg.vision_patch, grid(cfg)
    with jax.named_scope("vision"):
        patches = pixels.reshape(B, g, P, g, P, 3).transpose(0, 1, 3, 2, 4, 5)
        x = mm(cfg, patches.reshape(B, g * g, P * P * 3), p["patch_w"])
        x = x + p["patch_b"] + position_table(cfg, p["pos"])[None]
        ang = rope_2d_angles(cfg)

        def body(x, blk):
            return _block(cfg, x, blk, ang), None

        fn = jax.checkpoint(body) if cfg.remat else body
        x, _ = lax.scan(fn, x, p["blocks"])
        return layernorm(x, p["final_w"], p["final_b"], cfg.vision_norm_eps)


def project(cfg: ModelConfig, p: Dict, feats: jax.Array) -> jax.Array:
    """Patch features (B, g*g, Dv) -> LM image embeddings (B, (g/k)^2, D)."""
    B, _, Dv = feats.shape
    g, k = grid(cfg), cfg.vision_merge
    with jax.named_scope("projector"):
        x = layernorm(feats, p["ln_w"], p["ln_b"], cfg.vision_norm_eps)
        x = x.reshape(B, g // k, k, g // k, k, Dv).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(B, (g // k) ** 2, k * k * Dv)
        x = jax.nn.gelu(mm(cfg, x, p["w1"]) + p["b1"], approximate=False)
        return mm(cfg, x, p["w2"]) + p["b2"]


def image_embeds(cfg: ModelConfig, params: Dict, pixels: jax.Array) -> jax.Array:
    return project(cfg, params["projector"], encode(cfg, params["vision"], pixels))
