"""Plain reference for Kimi-VL-A3B training (``configs/kimi_vl_a3b.py``).

Straightforward float32 ``jax.numpy``, meant to run under
``jax.default_matmul_precision("highest")``: no scan, no remat, no blocked
attention, no sorting or grouped matmuls, no capacity. Layers are looped
over in Python; every held expert is computed for every token and weighted
by its gate (zero where the router did not choose it). It takes the
program's parameter tree (``lm.param_defs``) and computes the forward pass,
the loss, the gradients, one AdamW step and the router's bias update.

Equations: MoonViT (arXiv:2504.07491; patch embedding, bicubically resized
position table, pre-LayerNorm blocks with 2D RoPE, GELU-tanh MLP, final
LayerNorm), the 2x2 merger and MLP projector; the DeepSeek-V3 block
(arXiv:2412.19437): MLA with ``q_lora_rank`` null, sigmoid routing with a
correction bias in the choice, normalised and scaled weights, shared
experts, the sequence-wise balance loss, and the bias rule.

Departures from the published model, each shared with the program:

* the expert share: only experts ``[expert_offset, expert_offset +
  experts_held)`` are computed; the others' part of each routed output is
  left out (it lies on other chips of the deployment), the vocabulary is
  the configuration's slice, and depth is cut;
* RoPE on the MLA rope parts rotates halves (``rotate_half``); DeepSeek-V3
  rotates interleaved pairs, which is the same up to a fixed permutation of
  the rope columns of W_q and W_kva;
* RMSNorm weights are stored as ``1 + w`` (zeros at initialisation), so
  AdamW's weight decay pulls the scale towards 1, not 0;
* frames are square and of one size, given already normalised;
* the loss is the mean next-token cross entropy over the text positions
  (from the last image position on), plus ``seq_aux_weight`` times the
  balance loss summed over the MoE layers.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def layernorm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def attend(q, k, v, mask):
    """q, k (B,S,H,d), v (B,S,H,dv); mask (S,S) or None."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


# ---------------------------------------------------------------------------
# language model
# ---------------------------------------------------------------------------

def rope_half(x, pos, theta):
    """x (B,S,H,d): rotate (x1, x2) halves by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    ang = pos[:, None] * theta ** (-jnp.arange(0, d, 2, dtype=f32) / d)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def mla(cfg, p, x):
    B, S, _ = x.shape
    H, dn, dr, dv, R = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    pos = jnp.arange(S, dtype=f32)
    q = (x @ p["wq"]).reshape(B, S, H, dn + dr)
    kv_a = x @ p["wkv_a"]
    c_kv = rmsnorm(kv_a[..., :R], p["kv_norm"], cfg.norm_eps)
    kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, dn + dv)
    k_pe = rope_half(kv_a[..., None, R:], pos, cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], rope_half(q[..., dn:], pos, cfg.rope_theta)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_pe, H, axis=2)], -1)
    mask = jnp.tril(jnp.ones((S, S), bool))
    return attend(q, k, kv[..., dn:], mask).reshape(B, S, H * dv) @ p["wo"]


def swiglu(p, x):
    return (jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])) @ p["wo"]


def route(cfg, p, x, bias):
    """Router: scores (T,E), chosen experts (T,K), gates (T,K)."""
    scores = jax.nn.sigmoid(x @ p["router"])
    choice = scores if bias is None else scores + bias
    _, idx = jax.lax.top_k(choice, cfg.experts_per_token)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    return scores, idx, w


def moe(cfg, p, x, bias):
    """(y, balance loss, load (E,)) for x (B,S,D)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    xf = x.reshape(B * S, D)
    scores, idx, w = route(cfg, p, xf, bias)
    onehot = jax.nn.one_hot(idx, E, dtype=f32)                 # (T,K,E)
    gate = jnp.einsum("tk,tke->te", w, onehot)                  # (T,E)
    y = jnp.zeros_like(xf)
    for g in range(cfg.held_experts):
        e = cfg.expert_offset + g
        ex = {"wi_gate": p["we_gate"][g], "wi_up": p["we_up"][g],
              "wo": p["we_down"][g]}
        y = y + gate[:, e:e + 1] * swiglu(ex, xf)
    if cfg.num_shared_experts:
        y = y + swiglu(p["shared"], xf)
    # balance loss per sequence: f_i = E/(K S) * slots of i, P_i = mean s'_i
    counts = onehot.reshape(B, S * K, E).sum(1)
    f = jax.lax.stop_gradient(counts * E / (K * S))
    sn = scores / scores.sum(-1, keepdims=True)
    P = sn.reshape(B, S, E).mean(1)
    aux = jnp.mean(jnp.sum(f * P, -1))
    return y.reshape(B, S, D), aux, onehot.sum((0, 1))


# ---------------------------------------------------------------------------
# vision tower
# ---------------------------------------------------------------------------

def _cubic_weights(t):
    """PyTorch's bicubic convolution weights (a = -0.75) for taps at -1, 0,
    1, 2 around a sample ``t`` past the left tap."""
    a = -0.75
    x1, x2 = t + 1, 1 - t
    return [((a * x1 - 5 * a) * x1 + 8 * a) * x1 - 4 * a,
            ((a + 2) * t - (a + 3)) * t * t + 1,
            ((a + 2) * x2 - (a + 3)) * x2 * x2 + 1,
            ((a * (x2 + 1) - 5 * a) * (x2 + 1) + 8 * a) * (x2 + 1) - 4 * a]


def resize_axis(table, n_out, axis):
    """Bicubic resize of one axis, ``align_corners=False``, edges clamped."""
    n_in = table.shape[axis]
    rows = []
    for i in range(n_out):
        src = n_in / n_out * (i + 0.5) - 0.5
        i0 = math.floor(src)
        ws = _cubic_weights(src - i0)
        acc = 0.0
        for k, wk in zip(range(-1, 3), ws):
            acc = acc + wk * jnp.take(table, min(max(i0 + k, 0), n_in - 1), axis)
        rows.append(acc)
    return jnp.stack(rows, axis)


def rope_2d(x, g):
    """x (B, g*g, H, hd): pair j of consecutive elements turns by the
    column (j even) or row (j odd) times theta^(-4 (j // 2) / hd)."""
    hd = x.shape[-1]
    n = np.arange(g * g)
    freqs = 10_000.0 ** (-np.arange(0, hd, 4)[: hd // 4] / hd)
    ang = np.empty((g * g, hd // 2))
    ang[:, 0::2] = np.outer(n % g, freqs)
    ang[:, 1::2] = np.outer(n // g, freqs)
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * jnp.exp(
        1j * jnp.asarray(ang, f32))[None, :, None]
    return jnp.stack([z.real, z.imag], -1).reshape(x.shape)


def vision(cfg, p, pixels):
    B, Hpx = pixels.shape[0], pixels.shape[1]
    P = cfg.vision_patch
    g = Hpx // P
    Dv, H = cfg.vision_d_model, cfg.vision_heads
    eps = cfg.vision_norm_eps
    patches = jnp.stack([pixels[:, r * P:(r + 1) * P, c * P:(c + 1) * P].reshape(B, -1)
                         for r in range(g) for c in range(g)], 1)
    x = patches @ p["patch_w"] + p["patch_b"]
    pos = resize_axis(resize_axis(p["pos"], g, 0), g, 1).reshape(g * g, Dv)
    x = x + pos
    b = p["blocks"]
    for i in range(cfg.vision_layers):
        h = layernorm(x, b["ln0_w"][i], b["ln0_b"][i], eps)
        qkv = (h @ b["qkv_w"][i] + b["qkv_b"][i]).reshape(B, g * g, 3, H, Dv // H)
        q, k = rope_2d(qkv[:, :, 0], g), rope_2d(qkv[:, :, 1], g)
        a = attend(q, k, qkv[:, :, 2], None).reshape(B, g * g, Dv)
        x = x + a @ b["o_w"][i] + b["o_b"][i]
        h = layernorm(x, b["ln1_w"][i], b["ln1_b"][i], eps)
        h = jax.nn.gelu(h @ b["fc0_w"][i] + b["fc0_b"][i], approximate=True)
        x = x + h @ b["fc1_w"][i] + b["fc1_b"][i]
    return layernorm(x, p["final_w"], p["final_b"], eps)


def projector(cfg, p, feats):
    B, _, Dv = feats.shape
    k = cfg.vision_merge
    g = cfg.image_hw // cfg.vision_patch
    x = layernorm(feats, p["ln_w"], p["ln_b"], cfg.vision_norm_eps)
    x = x.reshape(B, g, g, Dv)
    merged = jnp.concatenate([x[:, i::k, j::k] for i in range(k) for j in range(k)],
                             -1).reshape(B, (g // k) ** 2, k * k * Dv)
    h = jax.nn.gelu(merged @ p["w1"] + p["b1"], approximate=False)
    return h @ p["w2"] + p["b2"]


# ---------------------------------------------------------------------------
# loss, gradients, one training step
# ---------------------------------------------------------------------------

def loss_and_load(cfg, params, batch, bias: Optional[jax.Array]) -> Tuple:
    """(total loss, (load (R, E), cross entropy, balance loss))."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    n = cfg.num_image_tokens
    img = projector(cfg, params["projector"],
                    vision(cfg, params["vision"], batch["pixels"]))
    x = jnp.concatenate([img, params["embed"][tokens[:, n:]]], 1)
    d = params["dense"]
    for i in range(cfg.first_dense_layers):
        li = jax.tree.map(lambda t: t[i], d)
        x = x + mla(cfg, li["attn"], rmsnorm(x, li["attn"]["norm"], cfg.norm_eps))
        x = x + swiglu(li["mlp"], rmsnorm(x, li["mlp"]["norm"], cfg.norm_eps))
    blk = params["blocks"]["blk0"]
    aux, loads = 0.0, []
    for i in range(cfg.num_layers - cfg.first_dense_layers):
        li = jax.tree.map(lambda t: t[i], blk)
        x = x + mla(cfg, li["attn"], rmsnorm(x, li["attn"]["norm"], cfg.norm_eps))
        y, a, load = moe(cfg, li["moe"], rmsnorm(x, li["moe"]["norm"], cfg.norm_eps),
                         None if bias is None else bias[i])
        x, aux = x + y, aux + a
        loads.append(load)
    logits = rmsnorm(x, params["final_norm"], cfg.norm_eps) @ params["head"]
    logp = jax.nn.log_softmax(logits[:, n - 1:-1], -1)
    nll = -jnp.take_along_axis(logp, tokens[:, n:, None], -1)
    xent = jnp.mean(nll)
    total = xent + cfg.seq_aux_weight * aux
    return total, (jnp.stack(loads), xent, aux)


def train_step(cfg, params, state, m, v, count, batch, lr, max_norm=1.0,
               b1=0.9, b2=0.95, eps=1e-8, wd=0.01):
    """One step: clip the gradient to ``max_norm``, AdamW, the bias rule.
    Returns (params, state, m, v, loss, clipped grads)."""
    (loss, (load, _, _)), g = jax.value_and_grad(
        loss_and_load, argnums=1, has_aux=True)(cfg, params, batch, state["bias"])
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9)), g)
    c = count + 1
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / (1 - b1 ** c)) / (jnp.sqrt(b / (1 - b2 ** c)) + eps)
                                  + wd * p), params, m, v)
    lo = cfg.expert_offset
    state = {"bias": state["bias"] + cfg.bias_update_rate
             * jnp.sign(load.mean(-1, keepdims=True) - load),
             "routed": state["routed"]
             + load[:, lo:lo + cfg.held_experts].astype(jnp.int32)}
    return params, state, m, v, loss, g


def initial_state(cfg) -> Dict:
    R = cfg.num_layers - cfg.first_dense_layers
    return {"bias": jnp.zeros((R, cfg.num_experts), f32),
            "routed": jnp.zeros((R, cfg.held_experts), jnp.int32)}
