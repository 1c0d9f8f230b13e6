"""Pure-JAX building blocks for the LM model zoo.

Everything here is functional: ``f(cfg, params, x, ...) -> y``. Activations
are bf16; softmax/norm/SSD accumulation is fp32. Tensors carry logical
sharding annotations (``repro.sharding.shard``) that resolve only under a
bound mesh.

Attention uses a *block-triangular* prefill schedule: a static python loop
over query blocks, each attending to the causally-reachable key prefix only.
This avoids the 2x dense-causal FLOP waste (visible in HLO, see
EXPERIMENTS.md §Perf) and bounds the fp32 score transient to
(B, H, q_block, k_len) — the jnp analogue of a flash-attention schedule, and
the shape the Pallas kernel (kernels/flash_attention.py) implements on TPU.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef
from repro.sharding import shard

f32 = jnp.float32

# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(f32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    return (out * (1.0 + w.astype(f32))).astype(x.dtype)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(f32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * w.astype(f32)
            + b.astype(f32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=f32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    freqs = rope_frequencies(x.shape[-1], theta)          # (hd/2,)
    ang = positions.astype(f32)[..., None] * freqs         # (B,S,hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(f32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embedding(length: int, dim: int) -> jax.Array:
    pos = jnp.arange(length, dtype=f32)[:, None]
    div = jnp.exp(-math.log(10_000.0) * jnp.arange(0, dim, 2, dtype=f32) / dim)
    ang = pos * div[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attn_param_defs(cfg: ModelConfig, layer_dim: Tuple[int, ...] = ()) -> Dict:
    D, Q, KV = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ax = tuple(["layer"] * len(layer_dim))
    d = {
        "norm": ParamDef(layer_dim + (D,), ax + ("embed",), "zeros"),
        "wq": ParamDef(layer_dim + (D, Q), ax + ("fsdp", "tensor"), "scaled"),
        "wk": ParamDef(layer_dim + (D, KV), ax + ("fsdp", "tensor"), "scaled"),
        "wv": ParamDef(layer_dim + (D, KV), ax + ("fsdp", "tensor"), "scaled"),
        "wo": ParamDef(layer_dim + (Q, D), ax + ("tensor", "fsdp"), "scaled"),
    }
    if cfg.qk_norm:
        d["q_norm"] = ParamDef(layer_dim + (cfg.head_dim,), ax + (None,), "zeros")
        d["k_norm"] = ParamDef(layer_dim + (cfg.head_dim,), ax + (None,), "zeros")
    return d


def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return scores
    return cap * jnp.tanh(scores / cap)


def _group_q(q: jax.Array, num_kv: int) -> jax.Array:
    """(B,T,H,hd) -> (B,T,K,G,hd): group query heads by their kv head."""
    B, T, H, hd = q.shape
    return q.reshape(B, T, num_kv, H // num_kv, hd)


def _qkv(cfg: ModelConfig, p: Dict, x: jax.Array, positions: jax.Array):
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, K, hd)
    v = (x @ p["wv"]).reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def _sdpa_block(q, k, v, mask, softcap: float, scale: float,
                bf16_chain: bool = False):
    """One (q-block x k-prefix) attention tile, grouped-query form.

    q: (B,T,K,G,hd); k/v: (B,L,K,hd); mask broadcastable to (B,K,G,T,L).

    Uses explicit batched dot_general over (B,K) with the G query group
    folded into the lhs rows — einsum's lowering broadcast-materializes K/V
    across G (in fp32), which for decode is G x 4-byte copies of the whole
    KV cache (measured in the dry-run HLO; EXPERIMENTS.md §Perf cell A).
    """
    B, T, K, G, hd = q.shape
    L = k.shape[1]
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B, K, G * T, hd)
    kf = k.transpose(0, 2, 1, 3)                       # (B,K,L,hd)
    scores = lax.dot_general(qf, kf, (((3,), (3,)), ((0, 1), (0, 1))),
                             preferred_element_type=f32) * scale
    scores = scores.reshape(B, K, G, T, L)
    scores = shard(scores, "batch", "kv_heads", None, None, None)
    scores = _softcap(scores, softcap)
    if bf16_chain:
        # subtract the fp32 row max FIRST, then drop to bf16: the exp/sum
        # chain over L runs at half the bytes with bounded relative error.
        m = jnp.max(jnp.where(mask, scores, -jnp.inf), axis=-1, keepdims=True
                    ) if mask is not None else jnp.max(scores, -1, keepdims=True)
        scores = (scores - m).astype(jnp.bfloat16)
        if mask is not None:
            scores = jnp.where(mask, scores, jnp.bfloat16(-1e30))
        e = jnp.exp(scores)
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
    else:
        if mask is not None:
            scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
    pf = probs.astype(q.dtype).reshape(B, K, G * T, L)
    vf = v.transpose(0, 2, 1, 3)                       # (B,K,L,hd)
    out = lax.dot_general(pf, vf, (((3,), (2,)), ((0, 1), (0, 1))))
    return out.reshape(B, K, G, T, hd).transpose(0, 3, 1, 2, 4)


def attention(cfg: ModelConfig, p: Dict, x: jax.Array, positions: jax.Array,
              *, is_local: bool = False, causal: bool = True,
              q_block: int = 1024) -> jax.Array:
    """Train / prefill attention with block-triangular schedule."""
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _qkv(cfg, p, x, positions)
    q = _group_q(q, K)
    scale = 1.0 / math.sqrt(hd)
    window = cfg.sliding_window if (is_local and cfg.sliding_window) else 0

    if not causal:                       # encoder: full bidirectional
        out = _sdpa_block(q, k, v, None, cfg.attn_logit_softcap, scale)
    else:
        q_block = min(q_block, S)
        n_blocks = max(1, S // q_block)
        outs = []
        for i in range(n_blocks):
            qs, qe = i * q_block, (i + 1) * q_block
            ks = 0 if window == 0 else max(0, qs - window)
            qb = q[:, qs:qe]
            kb, vb = k[:, ks:qe], v[:, ks:qe]
            qpos = jnp.arange(qs, qe)[:, None]
            kpos = jnp.arange(ks, qe)[None, :]
            mask = kpos <= qpos
            if window:
                mask &= kpos > qpos - window
            out = _sdpa_block(qb, kb, vb, mask[None, None, None],
                              cfg.attn_logit_softcap, scale)
            outs.append(out)
        out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]

    out = out.reshape(B, S, H * hd)
    out = out @ p["wo"]
    return shard(out, "batch", "seq", "embed")


def attention_decode(cfg: ModelConfig, p: Dict, x: jax.Array,
                     cache_k: jax.Array, cache_v: jax.Array,
                     position: jax.Array, *, is_local: bool = False,
                     ring: bool = False, scales=None):
    """Single-token decode. x:(B,1,D); cache:(B,S_len,K,hd); position:(B,).

    Cache stays SEQUENCE-MAJOR: a head-major (B,K,S,hd) layout was tried
    (it matches the attention dots) but the per-step scatter at a middle
    axis cost 3.9x more bytes than the leading-axis scatter — refuted
    hypothesis A3 in EXPERIMENTS.md §Perf.

    ``ring=True``: the cache is a ring buffer of length S_len <= window
    (sliding-window layers only) — K/V are stored RoPE'd at their absolute
    position, so wrap-around needs no re-rotation. Beyond-paper memory-term
    optimization (EXPERIMENTS.md §Perf): cuts both cache footprint and the
    per-step cache read bytes from S_max to window.
    """
    B = x.shape[0]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    S_len = cache_k.shape[1]
    q, k, v = _qkv(cfg, p, x, position[:, None])
    slot = (position % S_len) if ring else position
    bidx = jnp.arange(B)
    new_scales = None
    if scales is not None:                    # INT8 cache: quantize new row
        ks, vs = scales
        k_sc = jnp.max(jnp.abs(k[:, 0]).astype(f32), axis=-1) / 127.0 + 1e-8
        v_sc = jnp.max(jnp.abs(v[:, 0]).astype(f32), axis=-1) / 127.0 + 1e-8
        k_row = jnp.clip(jnp.round(k[:, 0] / k_sc[..., None]), -127, 127)
        v_row = jnp.clip(jnp.round(v[:, 0] / v_sc[..., None]), -127, 127)
        cache_k = cache_k.at[bidx, slot].set(k_row.astype(jnp.int8))
        cache_v = cache_v.at[bidx, slot].set(v_row.astype(jnp.int8))
        ks = ks.at[bidx, slot].set(k_sc.astype(ks.dtype))
        vs = vs.at[bidx, slot].set(v_sc.astype(vs.dtype))
        new_scales = (ks, vs)
    else:
        cache_k = cache_k.at[bidx, slot].set(k[:, 0])      # scatter update
        cache_v = cache_v.at[bidx, slot].set(v[:, 0])
    cache_k = shard(cache_k, "batch", "kv_seq", "kv_heads", None)
    cache_v = shard(cache_v, "batch", "kv_seq", "kv_heads", None)

    kpos = jnp.arange(S_len)[None, :]                      # (1,S_len)
    if ring:
        # absolute position stored in slot s: largest p' <= position with
        # p' % S_len == s; valid iff it has been written (p' >= 0). Window
        # containment is implied by S_len <= window.
        stored = position[:, None] - ((position[:, None] - kpos) % S_len)
        mask = stored >= 0
    else:
        mask = kpos <= position[:, None]
        if is_local and cfg.sliding_window:
            mask &= kpos > (position[:, None] - cfg.sliding_window)
    if scales is not None:
        # dequantized VIEWS feed the dots; the persistent cache stays int8
        kf = cache_k.astype(jnp.bfloat16) * new_scales[0][..., None].astype(
            jnp.bfloat16)
        vf = cache_v.astype(jnp.bfloat16) * new_scales[1][..., None].astype(
            jnp.bfloat16)
    else:
        kf, vf = cache_k, cache_v
    out = _sdpa_block(_group_q(q, K), kf, vf,
                      mask[:, None, None, None, :],
                      cfg.attn_logit_softcap, 1.0 / math.sqrt(hd),
                      bf16_chain=cfg.decode_bf16_scores)
    out = out.reshape(B, 1, H * hd) @ p["wo"]
    return shard(out, "batch", None, "embed"), cache_k, cache_v, new_scales


def cross_attention(cfg: ModelConfig, p: Dict, x: jax.Array,
                    enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """Decoder->encoder attention; enc_k/v precomputed (B, F, K, hd)."""
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    out = _sdpa_block(_group_q(q, K), enc_k, enc_v, None, 0.0,
                      1.0 / math.sqrt(hd))
    return out.reshape(B, S, H * hd) @ p["wo"]


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def mlp_param_defs(cfg: ModelConfig, layer_dim: Tuple[int, ...] = ()) -> Dict:
    D, F = cfg.d_model, cfg.d_ff
    ax = tuple(["layer"] * len(layer_dim))
    d = {
        "norm": ParamDef(layer_dim + (D,), ax + ("embed",), "zeros"),
        "wi_gate": ParamDef(layer_dim + (D, F), ax + ("fsdp", "tensor"), "scaled"),
        "wo": ParamDef(layer_dim + (F, D), ax + ("tensor", "fsdp"), "scaled"),
    }
    if cfg.mlp_gated:
        d["wi_up"] = ParamDef(layer_dim + (D, F), ax + ("fsdp", "tensor"), "scaled")
    return d


def _act(x: jax.Array, kind: str) -> jax.Array:
    return jax.nn.gelu(x) if kind == "gelu" else jax.nn.silu(x)


def mlp(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    h = _act(x @ p["wi_gate"], cfg.act)
    if cfg.mlp_gated:
        h = h * (x @ p["wi_up"])
    h = shard(h, "batch", "seq", "tensor")
    return shard(h @ p["wo"], "batch", "seq", "embed")


def moe_param_defs(cfg: ModelConfig, layer_dim: Tuple[int, ...] = ()) -> Dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ax = tuple(["layer"] * len(layer_dim))
    if cfg.router_scoring == "sigmoid":
        # the router over every expert; the weights of the held experts only
        G, F = cfg.held_experts, cfg.expert_ff
        d = {
            "norm": ParamDef(layer_dim + (D,), ax + ("embed",), "zeros"),
            "router": ParamDef(layer_dim + (D, E), ax + ("fsdp", None), "scaled"),
            "we_gate": ParamDef(layer_dim + (G, D, F),
                                ax + ("expert", "fsdp", "tensor"), "scaled"),
            "we_up": ParamDef(layer_dim + (G, D, F),
                              ax + ("expert", "fsdp", "tensor"), "scaled"),
            "we_down": ParamDef(layer_dim + (G, F, D),
                                ax + ("expert", "tensor", "fsdp"), "scaled"),
        }
        if cfg.num_shared_experts:
            d["shared"] = swiglu_param_defs(D, F * cfg.num_shared_experts,
                                            layer_dim)
        return d
    return {
        "norm": ParamDef(layer_dim + (D,), ax + ("embed",), "zeros"),
        "router": ParamDef(layer_dim + (D, E), ax + ("fsdp", None), "scaled"),
        "we_gate": ParamDef(layer_dim + (E, D, F), ax + ("expert", "fsdp", "tensor"), "scaled"),
        "we_up": ParamDef(layer_dim + (E, D, F), ax + ("expert", "fsdp", "tensor"), "scaled"),
        "we_down": ParamDef(layer_dim + (E, F, D), ax + ("expert", "tensor", "fsdp"), "scaled"),
    }


def moe(cfg: ModelConfig, p: Dict, x: jax.Array,
        bias: Optional[jax.Array] = None
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Token-choice MoE. Returns (output, balance loss, load): ``load`` is
    the token-slots routed to each of the router's experts, (E,) float32.

    ``router_scoring="sigmoid"`` takes the DeepSeek-V3 path (``moe_v3``,
    with the router state's correction ``bias``). Otherwise: softmax top-k
    with capacity-bounded index dispatch. That path avoids the (T, E, C)
    GShard one-hot dispatch tensor: tokens are gathered into an (E, C)
    index buffer (scatter with OOB drop), run through batched expert FFNs,
    and scatter-added back. FLOPs ~= topk * cf * T * 6DF; slots over an
    expert's capacity are dropped.
    """
    if cfg.router_scoring == "sigmoid":
        return moe_v3(cfg, p, x, bias)
    B, S, D = x.shape
    E, topk = cfg.num_experts, cfg.experts_per_token
    T = B * S
    C = max(1, int(math.ceil(T * topk * cfg.capacity_factor / E)))
    xf = x.reshape(T, D)

    logits = (xf @ p["router"]).astype(f32)                # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, topk)                   # (T,topk)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # load-balance loss (Switch): E * sum_e f_e * P_e
    f_e = jnp.mean(jnp.sum(jax.nn.one_hot(eidx, E, dtype=f32), axis=1), axis=0)
    P_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e * P_e)

    flat_e = eidx.reshape(-1)                              # (T*topk,)
    flat_g = gates.reshape(-1).astype(x.dtype)
    flat_t = jnp.arange(T * topk, dtype=jnp.int32) // topk
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)    # (T*topk, E)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)            # exclusive count
    pos = jnp.sum(pos * onehot, axis=-1)                   # (T*topk,) slot idx

    # Shard the capacity dim only when the dispatch buffers are large
    # (train/prefill): for decode-sized C the constraint forces padding and
    # extra collectives (measured regression on mixtral decode_32k, §Perf).
    cap_ax = "expert_cap" if C >= 4096 else None
    tok_buf = jnp.full((E, C), T, dtype=jnp.int32)
    tok_buf = tok_buf.at[flat_e, pos].set(flat_t, mode="drop")
    tok_buf = shard(tok_buf, "expert", cap_ax)
    gate_buf = jnp.zeros((E, C), dtype=x.dtype)
    gate_buf = gate_buf.at[flat_e, pos].set(flat_g, mode="drop")
    gate_buf = shard(gate_buf, "expert", cap_ax)

    xpad = jnp.concatenate([xf, jnp.zeros((1, D), x.dtype)], axis=0)
    xe = xpad[tok_buf]                                     # (E,C,D) gather
    xe = shard(xe, "expert", cap_ax, "embed")
    h = (_act(jnp.einsum("ecd,edf->ecf", xe, p["we_gate"]), cfg.act)
         * jnp.einsum("ecd,edf->ecf", xe, p["we_up"]))
    h = shard(h, "expert", cap_ax, "tensor")
    ye = jnp.einsum("ecf,efd->ecd", h, p["we_down"])
    ye = shard(ye * gate_buf[..., None], "expert", cap_ax, "embed")

    # combine TOKEN-major: each token gathers its top-k expert slots. The
    # scatter-add form (ypad.at[tok_buf].add) replicated the (E,C,D) buffer
    # and all-reduced 2x43 GB/device/step on the production mesh (§Perf
    # cell B, iteration B2); the gather lands already token-sharded.
    valid = pos < C                                        # dropped slots
    contrib = ye[flat_e, jnp.minimum(pos, C - 1)]          # (T*topk, D)
    contrib = jnp.where(valid[:, None], contrib, 0)
    y = shard(contrib.reshape(T, topk, D), "batch", None, "embed")
    y = jnp.sum(y, axis=1).reshape(B, S, D)
    return shard(y, "batch", "seq", "embed"), aux, f_e * T


def moe_v3(cfg: ModelConfig, p: Dict, x: jax.Array,
           bias: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """DeepSeek-V3 MoE over this chip's expert share.

    The router scores all ``num_experts`` with a sigmoid; the correction
    ``bias`` (router state) enters the top-k choice only; the chosen scores
    are normalised to sum 1 and scaled by ``routed_scaling_factor``. This
    chip computes the experts it holds (``routed_experts``) for the slots
    routed to them, and the shared experts for every token. Returns
    (output, sequence-wise balance loss, load over all experts).
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    xf = x.reshape(B * S, D)
    with jax.named_scope("moe.router"):
        scores = jax.nn.sigmoid(jnp.matmul(
            xf.astype(f32), p["router"].astype(f32),
            precision=lax.Precision.HIGHEST))                  # (T, E)
        choice = scores if bias is None else scores + bias.astype(f32)
        _, eidx = lax.top_k(choice, K)                         # (T, K)
        w = jnp.take_along_axis(scores, eidx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        w = w * cfg.routed_scaling_factor
        load = jnp.sum(jax.nn.one_hot(eidx, E, dtype=f32), axis=(0, 1))
        aux = seq_aux_loss(scores.reshape(B, S, E), eidx.reshape(B, S, K), E)
    y = routed_experts(cfg, p, xf, eidx, w)       # scopes itself: "moe.routed"
    if cfg.num_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + shared_experts(cfg, p, xf)
    return y.reshape(B, S, D).astype(x.dtype), aux, load


def seq_aux_loss(scores: jax.Array, eidx: jax.Array, E: int) -> jax.Array:
    """DeepSeek-V3's sequence-wise balance loss (arXiv:2412.19437, eqs.
    17-20), without its alpha: per sequence, sum_i f_i P_i with f_i = E/(K S)
    times the slots routed to expert i and P_i the mean over the sequence of
    the scores normalised over experts; the mean over sequences."""
    _, S, K = eidx.shape
    f = jnp.sum(jax.nn.one_hot(eidx, E, dtype=f32), axis=(1, 2)) * (E / (K * S))
    P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    return jnp.mean(jnp.sum(lax.stop_gradient(f) * P, axis=-1))


_GMM_OUT = "routed_gmm_out"


def routed_capacity(cfg: ModelConfig, slots):
    """Rows of the routed experts' dispatch and combine for ``slots``
    token-slots (T*K): twice the held experts' even share of them, rounded
    up to 512 rows, and at most ``slots``. From shapes alone; ``slots`` is
    an int, or an int array for the router state's count of fallbacks."""
    even = -(-slots * cfg.held_experts // cfg.num_experts)
    cap = -(-2 * even // 512) * 512
    return min(slots, cap) if isinstance(slots, int) else jnp.minimum(slots, cap)


def routed_experts(cfg: ModelConfig, p: Dict, xf: jax.Array, eidx: jax.Array,
                   w: jax.Array) -> jax.Array:
    """The held experts' part of the routed output, dropless.

    Every token-slot (T*K of them) is sorted by its expert: the slots of
    held experts first, grouped in expert order, then the rest. Grouped
    matmuls (``lax.ragged_dot``) run each group through its expert, and
    each slot's output, times its weight, is added to its token.

    Only the held slots are live, about T*K*G/E of them, so the dispatch
    and combine run over the first C rows of the sort, C =
    ``routed_capacity(cfg, T*K)``, with no (T*K, ...) array. A layer-step
    whose held slots exceed C runs the same code over all T*K rows instead
    (``lax.cond``): no slot is dropped under any imbalance, and both row
    counts give the same values and gradients. Where C = T*K there is one
    path and no branch.

    The rows past the groups are left undefined by the TPU's grouped
    matmul, in its output and in its gradient for the rows, so each grouped
    matmul's rows past the groups are zeroed on the way in (which zeroes
    that gradient) and on the way out.
    """
    T, K = eidx.shape
    G, lo = cfg.held_experts, cfg.expert_offset
    C = routed_capacity(cfg, T * K)
    with jax.named_scope("moe.routed"):
        local = eidx.reshape(-1) - lo
        key = jnp.where((local >= 0) & (local < G), local, G)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, G, dtype=jnp.int32), axis=0)
        cd = jnp.dtype(cfg.compute_dtype)
        we = {k: p[k].astype(cd) for k in ("we_gate", "we_up", "we_down")}
        fits = jnp.sum(sizes) <= C
    args = (we, xf, w, order, sizes)
    if C == T * K:
        return _routed_rows(cfg, C, *args)
    # A conditional hands its backward the residuals of both branches, so
    # each keeps few: the capacity path its grouped matmuls' outputs, the
    # full-size path nothing (it recomputes its forward in the backward).
    # The conditional stays outside the scope: on the device it spans the
    # branch it runs, whose ops carry the scope.
    keep_gmm = jax.checkpoint_policies.save_only_these_names(_GMM_OUT)
    within = jax.checkpoint(functools.partial(_routed_rows, cfg, C), policy=keep_gmm)
    full = jax.checkpoint(functools.partial(_routed_rows, cfg, T * K))
    return lax.cond(fits, within, full, *args)


@jax.named_scope("moe.routed")
def _routed_rows(cfg: ModelConfig, rows: int, p: Dict, xf: jax.Array,
                 w: jax.Array, order: jax.Array, sizes: jax.Array) -> jax.Array:
    """``routed_experts`` over the first ``rows`` rows of the sort, which
    hold every held slot: each held expert's SwiGLU over its group, and
    each live row, times its weight, added to its token in a (T, D)
    float32 result. The rows past the groups (``live`` false) are zeroed
    around each grouped matmul."""
    T, D = xf.shape
    K = w.shape[1]
    cd = jnp.dtype(cfg.compute_dtype)
    slot = order[:rows]
    live = jnp.arange(rows) < jnp.sum(sizes)

    def gmm(a, wt):
        a = jnp.where(live[:, None], a, jnp.zeros((), a.dtype))
        y = lax.ragged_dot(a, wt, sizes, preferred_element_type=cd)
        return checkpoint_name(jnp.where(live[:, None], y, jnp.zeros((), cd)), _GMM_OUT)

    xs = xf[slot // K].astype(cd)                              # (rows, D)
    h = jax.nn.silu(gmm(xs, p["we_gate"]).astype(f32)) * gmm(xs, p["we_up"])
    ys = gmm(h.astype(cd), p["we_down"])                       # (rows, D)
    wt = jnp.where(live, w.reshape(-1)[slot], 0.0)
    return jnp.zeros((T, D), f32).at[slot // K].add(ys.astype(f32) * wt[:, None])


def shared_experts(cfg: ModelConfig, p: Dict, xf: jax.Array) -> jax.Array:
    """The shared experts, one SwiGLU of ``num_shared_experts`` times the
    expert width, on every token."""
    return swiglu(cfg, p["shared"], xf)


# ---------------------------------------------------------------------------
# DeepSeek-V3 block pieces: mixed-precision matmuls, SwiGLU, MLA
# ---------------------------------------------------------------------------

def mm(cfg: ModelConfig, x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` with both operands in ``compute_dtype``, accumulated and
    returned in float32."""
    cd = jnp.dtype(cfg.compute_dtype)
    return jnp.matmul(x.astype(cd), w.astype(cd), preferred_element_type=f32)


def swiglu(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(mm(cfg, x, p["wi_gate"])) * mm(cfg, x, p["wi_up"])
    return mm(cfg, h, p["wo"])


def swiglu_param_defs(D: int, F: int, layer_dim: Tuple[int, ...] = ()) -> Dict:
    ax = tuple(["layer"] * len(layer_dim))
    return {
        "wi_gate": ParamDef(layer_dim + (D, F), ax + ("fsdp", "tensor"), "scaled"),
        "wi_up": ParamDef(layer_dim + (D, F), ax + ("fsdp", "tensor"), "scaled"),
        "wo": ParamDef(layer_dim + (F, D), ax + ("tensor", "fsdp"), "scaled"),
    }


def mla_param_defs(cfg: ModelConfig, layer_dim: Tuple[int, ...] = ()) -> Dict:
    D, H, R = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    ax = tuple(["layer"] * len(layer_dim))
    return {
        "norm": ParamDef(layer_dim + (D,), ax + ("embed",), "zeros"),
        "wq": ParamDef(layer_dim + (D, H * cfg.qk_head_dim),
                       ax + ("fsdp", "tensor"), "scaled"),
        "wkv_a": ParamDef(layer_dim + (D, R + cfg.qk_rope_head_dim),
                          ax + ("fsdp", None), "scaled"),
        "kv_norm": ParamDef(layer_dim + (R,), ax + (None,), "zeros"),
        "wkv_b": ParamDef(layer_dim + (R, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                          ax + (None, "tensor"), "scaled"),
        "wo": ParamDef(layer_dim + (H * cfg.v_head_dim, D),
                       ax + ("tensor", "fsdp"), "scaled"),
    }


def mla_rope(cfg: ModelConfig, q_pe: jax.Array, k_pe: jax.Array,
             positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """RoPE on the decoupled rope parts: q_pe (B,S,H,r), k_pe (B,S,1,r)."""
    return (apply_rope(q_pe, positions, cfg.rope_theta),
            apply_rope(k_pe, positions, cfg.rope_theta))


def mla(cfg: ModelConfig, p: Dict, x: jax.Array, positions: jax.Array,
        q_block: int = 256) -> jax.Array:
    """Multi-head latent attention, training form (DeepSeek-V2/V3 with
    ``q_lora_rank`` null): q = x W_q split into nope and rope parts;
    [c_kv, k_pe] = x W_kva; c_kv -> RMSNorm -> W_kvb -> k_nope, v; RoPE on
    q_pe and k_pe, k_pe shared by the heads; scale 1/sqrt(nope + rope);
    causal."""
    B, S, _ = x.shape
    H, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim)
    R = cfg.kv_lora_rank
    cd = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("mla"):
        q = mm(cfg, x, p["wq"]).reshape(B, S, H, dn + dr)
        kv_a = mm(cfg, x, p["wkv_a"])
        c_kv = rmsnorm(kv_a[..., :R], p["kv_norm"], cfg.norm_eps)
        kv = mm(cfg, c_kv, p["wkv_b"]).reshape(B, S, H, dn + dv)
        q_pe, k_pe = mla_rope(cfg, q[..., dn:], kv_a[..., None, R:], positions)
        q = jnp.concatenate([q[..., :dn], q_pe], axis=-1).astype(cd)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_pe, (B, S, H, dr))], axis=-1).astype(cd)
        out = blocked_attention(q, k, kv[..., dn:].astype(cd),
                                1.0 / math.sqrt(dn + dr), True, q_block)
        return mm(cfg, out.reshape(B, S, H * dv), p["wo"])


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
                      causal: bool, q_block: int) -> jax.Array:
    """Softmax attention by query blocks: q, k (B,S,H,dk), v (B,S,H,dv) ->
    (B,S,H,dv) float32. Scores and softmax are float32. A causal block
    attends to the key prefix it can reach. Each block is rematerialised,
    so the backward pass holds one block's scores at a time."""
    B, S, H, _ = q.shape
    qb = min(q_block, S)
    if S % qb:
        raise ValueError(f"sequence {S} is not a multiple of the block {qb}")

    def block(qi, ki, vi, start):
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, ki,
                       preferred_element_type=f32) * scale
        if causal:
            qpos = start + jnp.arange(qi.shape[1])[:, None]
            s = jnp.where(jnp.arange(ki.shape[1])[None, :] <= qpos, s, -1e30)
        pr = jax.nn.softmax(s, axis=-1).astype(vi.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", pr, vi, preferred_element_type=f32)

    outs = []
    for start in range(0, S, qb):
        end = start + qb if causal else S
        fn = jax.checkpoint(block, static_argnums=(3,))
        outs.append(fn(q[:, start:start + qb], k[:, :end], v[:, :end], start))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]

# ---------------------------------------------------------------------------
# Mamba-2 (SSD)
# ---------------------------------------------------------------------------

def ssm_param_defs(cfg: ModelConfig, layer_dim: Tuple[int, ...] = ()) -> Dict:
    D = cfg.d_model
    di, ds, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * ds
    ax = tuple(["layer"] * len(layer_dim))
    return {
        "norm": ParamDef(layer_dim + (D,), ax + ("embed",), "zeros"),
        "in_proj": ParamDef(layer_dim + (D, 2 * di + 2 * ds + nh),
                            ax + ("fsdp", "tensor"), "scaled"),
        "conv_w": ParamDef(layer_dim + (cfg.ssm_conv_width, conv_dim),
                           ax + (None, "tensor"), "scaled", scale=0.5),
        "conv_b": ParamDef(layer_dim + (conv_dim,), ax + ("tensor",), "zeros"),
        "A_log": ParamDef(layer_dim + (nh,), ax + (None,), "arange_neg"),
        "D_skip": ParamDef(layer_dim + (nh,), ax + (None,), "ones"),
        "dt_bias": ParamDef(layer_dim + (nh,), ax + (None,), "zeros"),
        "gate_norm": ParamDef(layer_dim + (di,), ax + ("tensor",), "zeros"),
        "out_proj": ParamDef(layer_dim + (di, D), ax + ("tensor", "fsdp"), "scaled"),
    }


def _segsum(x: jax.Array) -> jax.Array:
    """x: (..., Q) -> (..., Q, Q) lower-tri cumulative segment sums."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def _ssm_inputs(cfg: ModelConfig, p: Dict, x: jax.Array):
    """Shared in_proj + causal depthwise conv for train and decode paths."""
    di, ds, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    zxbcdt = x @ p["in_proj"]
    z, xBC, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * ds], axis=-1)
    return z, xBC, dt


def _causal_conv(xBC: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over (B, S, C); w: (K, C)."""
    K, C = w.shape
    out = lax.conv_general_dilated(
        xBC, w[:, None, :],                # (K, 1, C) kernel
        window_strides=(1,), padding=[(K - 1, 0)],
        dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=C)
    return jax.nn.silu(out + b)


def ssd(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    """Mamba-2 SSD block, chunked training/prefill form [arXiv:2405.21060]."""
    B, S, _ = x.shape
    di, ds, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    Q = min(cfg.ssm_chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    z, xBC, dt = _ssm_inputs(cfg, p, x)
    xBC = _causal_conv(xBC, p["conv_w"], p["conv_b"])
    xs, B_, C_ = jnp.split(xBC, [di, di + ds], axis=-1)

    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))  # (B,S,nh)
    A = -jnp.exp(p["A_log"].astype(f32))                             # (nh,)

    X = xs.reshape(B, S, nh, hd).astype(f32)
    Xd = X * dt[..., None]
    dA = (dt * A).reshape(B, nc, Q, nh).transpose(0, 3, 1, 2)        # (B,nh,nc,Q)
    Bc = B_.reshape(B, nc, Q, ds).astype(f32)
    Cc = C_.reshape(B, nc, Q, ds).astype(f32)
    Xc = Xd.reshape(B, nc, Q, nh, hd)

    A_cum = jnp.cumsum(dA, axis=-1)                                  # (B,nh,nc,Q)
    L = jnp.exp(_segsum(dA))                                         # (B,nh,nc,Q,Q)
    L = shard(L, "batch", "heads", None, None, None)
    Y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, L, Xc)

    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)                  # (B,nh,nc,Q)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay_states, Xc)
    chunk_sum = A_cum[..., -1]                                       # (B,nh,nc)
    pad = jnp.pad(chunk_sum, ((0, 0), (0, 0), (1, 0)))
    decay_chunk = jnp.exp(_segsum(pad))                              # (B,nh,nc+1,nc+1)
    init = jnp.zeros((B, 1, nh, hd, ds), f32)
    all_states = jnp.concatenate([init, states], axis=1)
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, all_states)
    prev_states = new_states[:, :-1]                                 # (B,nc,nh,hd,ds)

    out_decay = jnp.exp(A_cum)                                       # (B,nh,nc,Q)
    Y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, prev_states, out_decay)
    Y = (Y_diag + Y_off).reshape(B, S, nh, hd)
    Y = Y + p["D_skip"].astype(f32)[None, None, :, None] * X
    y = Y.reshape(B, S, di).astype(x.dtype)

    y = rmsnorm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    return shard(y @ p["out_proj"], "batch", "seq", "embed")


def ssd_decode(cfg: ModelConfig, p: Dict, x: jax.Array,
               conv_state: jax.Array, ssm_state: jax.Array
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token SSD step. x:(B,1,D); conv_state:(B,K-1,conv_dim);
    ssm_state:(B,nh,hd,ds)."""
    B = x.shape[0]
    di, ds, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    z, xBC, dt = _ssm_inputs(cfg, p, x)                    # (B,1,*)
    window = jnp.concatenate([conv_state, xBC], axis=1)    # (B,K,conv)
    conv_out = jnp.einsum("bkc,kc->bc", window.astype(f32),
                          p["conv_w"].astype(f32)) + p["conv_b"].astype(f32)
    xBC = jax.nn.silu(conv_out)[:, None, :].astype(x.dtype)
    new_conv_state = window[:, 1:]

    xs, B_, C_ = jnp.split(xBC, [di, di + ds], axis=-1)
    dt = jax.nn.softplus(dt[:, 0].astype(f32) + p["dt_bias"].astype(f32))  # (B,nh)
    A = -jnp.exp(p["A_log"].astype(f32))
    dA = jnp.exp(dt * A)                                   # (B,nh)
    X = xs[:, 0].reshape(B, nh, hd).astype(f32)
    Bv = B_[:, 0].astype(f32)                              # (B,ds)
    Cv = C_[:, 0].astype(f32)
    new_ssm = (ssm_state * dA[..., None, None]
               + dt[..., None, None] * X[..., None] * Bv[:, None, None, :])
    Y = jnp.einsum("bhpn,bn->bhp", new_ssm, Cv)
    Y = Y + p["D_skip"].astype(f32)[None, :, None] * X
    y = Y.reshape(B, 1, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    return shard(y @ p["out_proj"], "batch", None, "embed"), new_conv_state, new_ssm
