"""Plain reference for Kimi-VL-A3B training, the benchmark's own.

Written from the published descriptions in straightforward ``jax.numpy``;
it imports nothing of the system under test and takes nothing it has made.
The architecture and its cut come from the configuration file
(``configs/kimi_vl_a3b.json``), the weights from the seed. No scan, no
remat, no blocked attention, no sorting or grouped matmuls, no capacity:
layers are looped over in Python, attention is one masked softmax over the
whole sequence, and every held expert runs on every token, weighted by its
gate (zero where the router did not choose it).

* MoonViT (arXiv:2504.07491 and the model's published ``vision_config``):
  14x14 patches embedded with a bias; a learned 64x64 position table,
  resized bicubically to the patch grid (PyTorch's ``interpolate``, a =
  -0.75, half-pixel centres, edges clamped); pre-LayerNorm blocks of
  attention (fused qkv with bias, 2D RoPE: pair 2j of a head turns with the
  patch's column and pair 2j+1 with its row, at theta^(-4j/hd)) and a GELU
  (tanh) MLP; a final LayerNorm. The merger LayerNorms each patch and
  concatenates each 2x2 square; the projector is Linear, GELU (erf), Linear.
* The DeepSeek-V3 block (arXiv:2412.19437): MLA with ``q_lora_rank`` null
  (q = x W_q in nope and rope parts; [c_kv, k_pe] = x W_kva; RMSNorm(c_kv)
  W_kvb gives k_nope and v; RoPE on q_pe and on k_pe, shared by the heads;
  scale 1/sqrt(nope + rope); causal), a dense SwiGLU layer first, then MoE
  layers: sigmoid scores over ``router_width`` experts, the correction bias
  in the top-k choice only, the chosen scores normalised and scaled by
  ``routed_scaling_factor``, SwiGLU experts, ``n_shared_experts`` shared
  experts as one SwiGLU of that many times the expert width; the
  sequence-wise balance loss with ``seq_aux_alpha``; the bias rule
  b_i += gamma * sign(mean load - load_i).

At the stated precision (``compute_dtype``): matmul inputs in bfloat16 with
float32 sums, the router's matmul in float32 at the highest precision,
norms, softmax and loss in float32; the residual stream in the parameters'
dtype (float32; the control casts the parameters to bfloat16).

Departures from the published model, each also made by the system under
test:

* the chip's share: only the held experts (``n_routed_experts`` of
  ``router_width``, from expert 0) are computed, the others' part of each
  routed output is left out; the vocabulary is the file's slice; depth cut;
* RoPE in MLA rotates halves (DeepSeek-V3 rotates interleaved pairs: the
  same up to a fixed permutation of the rope columns of W_q and W_kva);
* RMSNorm weights are stored as ``1 + w``, zeros at initialisation;
* frames are square, of the file's ``image_size``, already normalised;
  their 256 embeddings take LM positions 0-255;
* the loss is the mean next-token cross entropy over the text positions,
  from the last image position on, plus alpha times the balance loss summed
  over the MoE layers;
* the optimiser is AdamW (b1 0.9, b2 0.95, eps 1e-8, weight decay 0.01 on
  every leaf) after clipping the global gradient norm to 1.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
B1, B2, EPS, WD = 0.9, 0.95, 1e-8, 0.01


def sizes(cfg: Dict) -> Dict:
    v = cfg["vision_config"]
    return dict(
        D=cfg["hidden_size"], V=cfg["vocab_size"], F=cfg["intermediate_size"],
        H=cfg["num_attention_heads"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"], R=cfg["kv_lora_rank"],
        Fe=cfg["moe_intermediate_size"], E=cfg["router_width"],
        G=cfg["n_routed_experts"], K=cfg["num_experts_per_tok"],
        Fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        Ld=cfg["first_k_dense_replace"],
        Lm=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        P=v["patch_size"], Dv=v["hidden_size"], Hv=v["num_attention_heads"],
        Fv=v["intermediate_size"], Lv=v["num_hidden_layers"],
        Gp=v["init_pos_emb_height"], k=v["merge_kernel_size"][0],
        g=cfg["image_size"] // v["patch_size"])


# ---------------------------------------------------------------------------
# parameters: the program's tree, the benchmark's initialisation
# ---------------------------------------------------------------------------

def shapes(cfg: Dict) -> Dict:
    """Each parameter's shape and initialiser, ``(shape, init, fan_in)``:
    ``normal`` is N(0, 0.02), ``scaled`` N(0, 1/fan_in)."""
    s = sizes(cfg)
    D, H = s["D"], s["H"]

    def w(*shape, fan=None):
        return (shape, "scaled", fan if fan is not None else shape[-2])

    def z(*shape):
        return (shape, "zeros", 0)

    def o(*shape):
        return (shape, "ones", 0)

    def mla(n):
        return {"norm": z(n, D), "wq": w(n, D, H * (s["dn"] + s["dr"])),
                "wkv_a": w(n, D, s["R"] + s["dr"]), "kv_norm": z(n, s["R"]),
                "wkv_b": w(n, s["R"], H * (s["dn"] + s["dv"])),
                "wo": w(n, H * s["dv"], D)}

    def swiglu(n, f):
        return {"wi_gate": w(n, D, f), "wi_up": w(n, D, f), "wo": w(n, f, D)}

    n, Dv, Fv, Lv = s["Lm"], s["Dv"], s["Fv"], s["Lv"]
    Dm = Dv * s["k"] ** 2
    return {
        "embed": ((s["V"], D), "normal", 0),
        "final_norm": z(D),
        "head": w(D, s["V"]),
        "dense": {"attn": mla(s["Ld"]),
                  "mlp": {"norm": z(s["Ld"], D), **swiglu(s["Ld"], s["F"])}},
        "blocks": {"blk0": {"attn": mla(n), "moe": {
            "norm": z(n, D), "router": w(n, D, s["E"]),
            "we_gate": w(n, s["G"], D, s["Fe"]), "we_up": w(n, s["G"], D, s["Fe"]),
            "we_down": w(n, s["G"], s["Fe"], D), "shared": swiglu(n, s["Fs"])}}},
        "vision": {
            "patch_w": w(s["P"] * s["P"] * 3, Dv), "patch_b": z(Dv),
            "pos": ((s["Gp"], s["Gp"], Dv), "normal", 0),
            "blocks": {"ln0_w": o(Lv, Dv), "ln0_b": z(Lv, Dv),
                       "qkv_w": w(Lv, Dv, 3 * Dv), "qkv_b": z(Lv, 3 * Dv),
                       "o_w": w(Lv, Dv, Dv), "o_b": z(Lv, Dv),
                       "ln1_w": o(Lv, Dv), "ln1_b": z(Lv, Dv),
                       "fc0_w": w(Lv, Dv, Fv), "fc0_b": z(Lv, Fv),
                       "fc1_w": w(Lv, Fv, Dv), "fc1_b": z(Lv, Dv)},
            "final_w": o(Dv), "final_b": z(Dv)},
        "projector": {"ln_w": o(Dv), "ln_b": z(Dv), "w1": w(Dm, Dm), "b1": z(Dm),
                      "w2": w(Dm, D), "b2": z(D)},
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def init_params(cfg: Dict, key) -> Dict:
    spec = shapes(cfg)
    leaves, tree = jax.tree.flatten(spec, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (shape, init, fan), k in zip(leaves, keys):
        if init == "zeros":
            out.append(jnp.zeros(shape, f32))
        elif init == "ones":
            out.append(jnp.ones(shape, f32))
        else:
            std = 0.02 if init == "normal" else 1.0 / math.sqrt(fan)
            out.append(jax.random.normal(k, shape, f32) * std)
    return jax.tree.unflatten(tree, out)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _mm(cfg, a, b):
    cd = jnp.dtype(cfg["compute_dtype"])
    return jnp.matmul(a.astype(cd), b.astype(cd), preferred_element_type=f32)


def rmsnorm(x, w, eps):
    xf = x.astype(f32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * (1 + w.astype(f32))).astype(x.dtype)


def layernorm(x, w, b, eps):
    xf = x.astype(f32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    return ((xf - mu) / jnp.sqrt(var + eps) * w.astype(f32)
            + b.astype(f32)).astype(x.dtype)


def attend(cfg, q, k, v, causal):
    """q, k (B,S,H,d), v (B,S,H,dv): one masked softmax, float32 scores."""
    cd = jnp.dtype(cfg["compute_dtype"])
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(cd), k.astype(cd),
                   preferred_element_type=f32) / math.sqrt(q.shape[-1])
    if causal:
        S = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(cd), v.astype(cd),
                      preferred_element_type=f32)


def rope_half(x, theta):
    """Rotate the (first, second) halves of x (B,S,H,d) by position."""
    S, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(S, dtype=f32)[:, None] * theta ** (-jnp.arange(0, d, 2, dtype=f32) / d)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x = x.astype(f32)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def mla(cfg, p, x):
    s = sizes(cfg)
    B, S, _ = x.shape
    H, dn, dr, dv, R = s["H"], s["dn"], s["dr"], s["dv"], s["R"]
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    q = _mm(cfg, x, p["wq"]).reshape(B, S, H, dn + dr)
    kv_a = _mm(cfg, x, p["wkv_a"])
    c_kv = rmsnorm(kv_a[..., :R], p["kv_norm"], eps)
    kv = _mm(cfg, c_kv, p["wkv_b"]).reshape(B, S, H, dn + dv)
    k_pe = rope_half(kv_a[..., None, R:], theta)
    q = jnp.concatenate([q[..., :dn], rope_half(q[..., dn:], theta)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_pe, H, axis=2)], -1)
    out = attend(cfg, q, k, kv[..., dn:], True)
    return _mm(cfg, out.reshape(B, S, H * dv), p["wo"])


def swiglu(cfg, p, x):
    h = jax.nn.silu(_mm(cfg, x, p["wi_gate"])) * _mm(cfg, x, p["wi_up"])
    return _mm(cfg, h, p["wo"])


def moe(cfg, p, x, bias):
    """(y, balance loss, load over every expert) for x (B,S,D)."""
    s = sizes(cfg)
    B, S, D = x.shape
    E, K = s["E"], s["K"]
    xf = x.reshape(B * S, D)
    scores = jax.nn.sigmoid(jnp.matmul(xf.astype(f32), p["router"].astype(f32),
                                       precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + bias, K)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    onehot = jax.nn.one_hot(idx, E, dtype=f32)                  # (T,K,E)
    gate = jnp.einsum("tk,tke->te", w, onehot, precision=jax.lax.Precision.HIGHEST)
    y = jnp.zeros((B * S, D), f32)
    for e in range(s["G"]):
        ex = {"wi_gate": p["we_gate"][e], "wi_up": p["we_up"][e],
              "wo": p["we_down"][e]}
        y = y + gate[:, e:e + 1] * swiglu(cfg, ex, xf)
    y = y + swiglu(cfg, p["shared"], xf)
    f = jax.lax.stop_gradient(onehot.reshape(B, S * K, E).sum(1) * E / (K * S))
    P = (scores / scores.sum(-1, keepdims=True)).reshape(B, S, E).mean(1)
    aux = jnp.mean(jnp.sum(f * P, -1))
    return y.reshape(B, S, D).astype(x.dtype), aux, onehot.sum((0, 1))


def _cubic(t):
    a = -0.75
    t = abs(t)
    if t <= 1:
        return (a + 2) * t ** 3 - (a + 3) * t ** 2 + 1
    return a * t ** 3 - 5 * a * t ** 2 + 8 * a * t - 4 * a if t < 2 else 0.0


def resize(table, n_out, axis):
    """Bicubic resize of one axis (align_corners False, edges clamped)."""
    n_in = table.shape[axis]
    rows = []
    for i in range(n_out):
        src = n_in / n_out * (i + 0.5) - 0.5
        i0 = math.floor(src)
        acc = 0.0
        for j in range(i0 - 1, i0 + 3):
            acc = acc + _cubic(src - j) * jnp.take(table, min(max(j, 0), n_in - 1), axis)
        rows.append(acc)
    return jnp.stack(rows, axis)


def rope_2d(x, g, theta):
    """x (B, g*g, H, hd): pair j turns by the column (even j) or row (odd j)."""
    hd = x.shape[-1]
    n = np.arange(g * g)
    freqs = theta ** (-np.arange(0, hd, 4)[: hd // 4] / hd)
    ang = np.empty((g * g, hd // 2))
    ang[:, 0::2] = np.outer(n % g, freqs)
    ang[:, 1::2] = np.outer(n // g, freqs)
    x = x.astype(f32)
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * jnp.exp(
        1j * jnp.asarray(ang, f32))[None, :, None]
    return jnp.stack([z.real, z.imag], -1).reshape(x.shape)


def vision(cfg, p, pixels, act):
    s = sizes(cfg)
    v = cfg["vision_config"]
    B = pixels.shape[0]
    P, g, Dv, H = s["P"], s["g"], s["Dv"], s["Hv"]
    eps = v["layer_norm_eps"]
    patches = jnp.stack([pixels[:, r * P:(r + 1) * P, c * P:(c + 1) * P].reshape(B, -1)
                         for r in range(g) for c in range(g)], 1)
    pos = resize(resize(p["pos"].astype(f32), g, 0), g, 1).reshape(g * g, Dv)
    x = (_mm(cfg, patches, p["patch_w"]) + p["patch_b"] + pos).astype(act)
    b = p["blocks"]
    for i in range(s["Lv"]):
        h = layernorm(x, b["ln0_w"][i], b["ln0_b"][i], eps)
        qkv = (_mm(cfg, h, b["qkv_w"][i]) + b["qkv_b"][i]).reshape(B, g * g, 3, H, Dv // H)
        q = rope_2d(qkv[:, :, 0], g, v["rope_theta"])
        k = rope_2d(qkv[:, :, 1], g, v["rope_theta"])
        a = attend(cfg, q, k, qkv[:, :, 2], False).reshape(B, g * g, Dv)
        x = x + (_mm(cfg, a, b["o_w"][i]) + b["o_b"][i]).astype(act)
        h = layernorm(x, b["ln1_w"][i], b["ln1_b"][i], eps)
        h = jax.nn.gelu(_mm(cfg, h, b["fc0_w"][i]) + b["fc0_b"][i], approximate=True)
        x = x + (_mm(cfg, h, b["fc1_w"][i]) + b["fc1_b"][i]).astype(act)
    return layernorm(x, p["final_w"], p["final_b"], eps)


def projector(cfg, p, feats):
    s = sizes(cfg)
    B, _, Dv = feats.shape
    g, k = s["g"], s["k"]
    x = layernorm(feats, p["ln_w"], p["ln_b"], cfg["vision_config"]["layer_norm_eps"])
    x = x.reshape(B, g, g, Dv)
    x = jnp.concatenate([x[:, i::k, j::k] for i in range(k) for j in range(k)],
                        -1).reshape(B, (g // k) ** 2, k * k * Dv)
    h = jax.nn.gelu(_mm(cfg, x, p["w1"]) + p["b1"], approximate=False)
    return _mm(cfg, h, p["w2"]) + p["b2"]


def loss_and_load(cfg, params, batch, bias):
    """(loss, load (MoE layers, E)) of one (micro)batch: ``pixels`` (B, H,
    W, 3) and ``tokens`` (B, S); the image embeddings take positions
    0..n-1, whose tokens are placeholders."""
    s = sizes(cfg)
    eps = cfg["rms_norm_eps"]
    act = params["embed"].dtype
    tokens = batch["tokens"]
    img = projector(cfg, params["projector"],
                    vision(cfg, params["vision"], batch["pixels"], act))
    n = img.shape[1]
    x = jnp.concatenate([img.astype(act), params["embed"][tokens[:, n:]]], 1)
    for i in range(s["Ld"]):
        li = jax.tree.map(lambda t: t[i], params["dense"])
        x = x + mla(cfg, li["attn"], rmsnorm(x, li["attn"]["norm"], eps)).astype(act)
        x = x + swiglu(cfg, li["mlp"], rmsnorm(x, li["mlp"]["norm"], eps)).astype(act)
    aux, loads = 0.0, []
    for i in range(s["Lm"]):
        li = jax.tree.map(lambda t: t[i], params["blocks"]["blk0"])
        x = x + mla(cfg, li["attn"], rmsnorm(x, li["attn"]["norm"], eps)).astype(act)
        y, a, load = moe(cfg, li["moe"], rmsnorm(x, li["moe"]["norm"], eps), bias[i])
        x, aux = x + y, aux + a
        loads.append(load)
    logits = _mm(cfg, rmsnorm(x[:, n - 1:-1], params["final_norm"], eps), params["head"])
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, tokens[:, n:, None], -1)[..., 0]
    return jnp.mean(nll) + cfg["seq_aux_alpha"] * aux, jnp.stack(loads)


# ---------------------------------------------------------------------------
# a training step, by microbatches
# ---------------------------------------------------------------------------

def make_step(cfg: Dict):
    """The reference's step ``(params, moments, bias, batch, count, lr,
    micro) -> (params, bias, loss, clipped grads or None)``. Gradients are
    summed over microbatches of ``micro`` rows on the device; AdamW's m and
    v, float32, wait on the host while they do (``moments``, a list the
    step refills), so that the reference fits on one chip."""
    grad_mb = jax.jit(jax.value_and_grad(partial(loss_and_load, cfg), has_aux=True))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)

    @partial(jax.jit, donate_argnums=0)
    def clip(g, n_mb):
        g = jax.tree.map(lambda x: x.astype(f32) / n_mb, g)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        return jax.tree.map(lambda x: x * jnp.minimum(1.0, 1.0 / jnp.maximum(norm, 1e-9)), g)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def adamw(p, m, v, g, count, lr):
        c = count.astype(f32)
        bc1, bc2 = 1 - B1 ** c, 1 - B2 ** c

        def upd(p, m, v, g):
            m = B1 * m + (1 - B1) * g
            v = B2 * v + (1 - B2) * g * g
            step = (m / bc1) / (jnp.sqrt(v / bc2) + EPS) + WD * p.astype(f32)
            return (p.astype(f32) - lr * step).astype(p.dtype), m, v

        out = jax.tree.map(upd, p, m, v, g)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2)

    def step(params, moments, bias, batch, count, lr, micro, keep_grad=False):
        """``keep_grad``: also return the clipped gradient, on the host."""
        rows = batch["tokens"].shape[0]
        acc, losses, load = None, [], 0.0
        for r in range(0, rows, micro):
            mb = {k: jnp.asarray(x[r:r + micro]) for k, x in batch.items()}
            (loss, ld), g = grad_mb(params, mb, bias)
            g = jax.tree.map(lambda x: x.astype(f32), g)
            acc = g if acc is None else add(acc, g)
            losses.append(float(loss))
            load = load + ld
        g = clip(acc, jnp.float32(rows // micro))
        del acc
        g_host = jax.device_get(g) if keep_grad else None
        m, v = jax.device_put(moments)
        moments.clear()
        p, m, v = adamw(params, m, v, g, jnp.int32(count), jnp.float32(lr))
        del g
        moments.extend(jax.device_get(x) for x in (m, v))
        bias = bias + cfg["bias_update_speed"] * jnp.sign(
            load.mean(-1, keepdims=True) - load)
        return p, bias, float(np.mean(losses)), g_host

    return step

