"""Unified LM zoo: one scan-over-layers transformer covering all 10 assigned
architectures (dense / MoE / SSM / hybrid / enc-dec / VLM-stub) and the
DeepSeek-V3 block (MLA, sigmoid-routed MoE with shared experts over an
expert share, leading dense layers) under Kimi-VL's MoonViT tower
(``models/vision.py``).

Heterogeneous stacks (gemma2 local/global alternation, jamba 1:7 attn:ssm +
alternating MoE) are handled with a *period block*: the layer pattern repeats
every ``lcm(local_global, attn, moe)`` layers, so parameters are stacked as
``num_layers // period`` repeats of a ``period``-sublayer block and the stack
is executed with ``lax.scan`` over repeats (static python loop over the
sublayers inside). This keeps HLO size O(1) in depth — required both for the
1-core-CPU compile budget here and for real compile times at 1000+ nodes.

Public entry points (all pure functions):
  * ``param_defs(cfg)``                          — ParamDef pytree
  * ``forward(cfg, params, tokens, ...)``        — train / prefill logits
  * ``loss_and_load(cfg, params, batch, bias)``  — training loss and MoE load
  * ``init_router_state`` / ``update_router_state`` — the router's bias rule
  * ``init_cache(cfg, batch, s_max)`` + ``decode_step(...)`` — serving
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import vision
from repro.models.params import ParamDef
from repro.sharding import shard

f32 = jnp.float32


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------

def block_period(cfg: ModelConfig) -> int:
    """Length of the repeating layer pattern."""
    p = 1
    if cfg.local_global_period:
        p = math.lcm(p, cfg.local_global_period)
    if cfg.attn_period:
        p = math.lcm(p, cfg.attn_period)
    if cfg.num_experts:
        p = math.lcm(p, cfg.moe_period)
    if stack_layers(cfg) % p != 0:
        raise ValueError(f"{cfg.name}: {stack_layers(cfg)} stacked layers not "
                         f"a multiple of layer pattern period {p}")
    return p


def stack_layers(cfg: ModelConfig) -> int:
    """Layers of the scanned stack: all but the leading dense ones."""
    return cfg.num_layers - cfg.first_dense_layers


def num_repeats(cfg: ModelConfig) -> int:
    return stack_layers(cfg) // block_period(cfg)


def sublayer_kind(cfg: ModelConfig, j: int) -> Dict[str, bool]:
    """Static description of sublayer ``j`` of the period block.

    Pattern positions are period-aligned by construction (lcm), so the kind
    of absolute layer ``i`` depends only on ``i % period``.
    """
    return dict(
        attn=cfg.is_attn_layer(j),
        ssm=(not cfg.is_attn_layer(j)) and cfg.ssm_state > 0,
        moe=cfg.is_moe_layer(j),
        local=cfg.is_local_layer(j),
        mlp=cfg.d_ff > 0 and not cfg.is_moe_layer(j),
    )


# ---------------------------------------------------------------------------
# parameter definitions
# ---------------------------------------------------------------------------

def _sublayer_defs(cfg: ModelConfig, j: int, R: int) -> Dict:
    kind = sublayer_kind(cfg, j)
    ld = (R,)
    d: Dict[str, Dict] = {}
    if kind["attn"]:
        d["attn"] = (L.mla_param_defs(cfg, ld) if cfg.mla
                     else L.attn_param_defs(cfg, ld))
        if cfg.sandwich_norm:
            d["attn"]["post_norm"] = ParamDef(ld + (cfg.d_model,),
                                              ("layer", "embed"), "zeros")
    if kind["ssm"]:
        d["ssm"] = L.ssm_param_defs(cfg, ld)
    if kind["moe"]:
        d["moe"] = L.moe_param_defs(cfg, ld)
    elif kind["mlp"]:
        d["mlp"] = L.mlp_param_defs(cfg, ld)
    if (kind["moe"] or kind["mlp"]) and cfg.sandwich_norm:
        key = "moe" if kind["moe"] else "mlp"
        d[key]["post_norm"] = ParamDef(ld + (cfg.d_model,),
                                       ("layer", "embed"), "zeros")
    if cfg.cross_attention:
        d["xattn"] = L.attn_param_defs(cfg, ld)
    return d


def param_defs(cfg: ModelConfig) -> Dict:
    D, V = cfg.d_model, cfg.vocab_size
    R, period = num_repeats(cfg), block_period(cfg)
    defs: Dict = {
        "embed": ParamDef((V, D), ("tensor", "fsdp"), "normal"),
        "final_norm": ParamDef((D,), ("embed",), "zeros"),
        "blocks": {f"blk{j}": _sublayer_defs(cfg, j, R) for j in range(period)},
    }
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((D, V), ("fsdp", "tensor"), "scaled")
    if cfg.first_dense_layers:
        ld = (cfg.first_dense_layers,)
        defs["dense"] = {"attn": L.mla_param_defs(cfg, ld),
                         "mlp": L.mlp_param_defs(cfg, ld)}
    if cfg.vision_layers:
        defs["vision"] = vision.param_defs(cfg)
        defs["projector"] = vision.projector_defs(cfg)
    if cfg.encoder_layers:
        E = cfg.encoder_layers
        enc = {
            "attn": L.attn_param_defs(cfg, (E,)),
            "mlp": L.mlp_param_defs(cfg, (E,)),
        }
        defs["encoder"] = {"layers": enc,
                           "final_norm": ParamDef((D,), ("embed",), "zeros")}
    if cfg.param_dtype != "bfloat16":
        defs = jax.tree.map(lambda d: dataclasses.replace(d, dtype=cfg.param_dtype),
                            defs, is_leaf=lambda d: isinstance(d, ParamDef))
    return defs


# ---------------------------------------------------------------------------
# block application (shared by train/prefill and decode)
# ---------------------------------------------------------------------------

def _apply_sublayer(cfg: ModelConfig, kind: Dict, p: Dict, x: jax.Array,
                    positions: jax.Array, aux: jax.Array,
                    enc_kv: Optional[Tuple] = None,
                    bias: Optional[jax.Array] = None):
    """Pre-norm residual sublayer (train / prefill form). Returns (x, aux,
    load): ``load`` is the MoE layer's slots by expert, or None."""
    load = None
    if kind["attn"]:
        h = L.rmsnorm(x, p["attn"]["norm"], cfg.norm_eps)
        if cfg.mla:
            h = L.mla(cfg, p["attn"], h, positions)
        else:
            h = L.attention(cfg, p["attn"], h, positions, is_local=kind["local"])
        if cfg.sandwich_norm:
            h = L.rmsnorm(h, p["attn"]["post_norm"], cfg.norm_eps)
        x = x + h
    elif kind["ssm"]:
        h = L.rmsnorm(x, p["ssm"]["norm"], cfg.norm_eps)
        x = x + L.ssd(cfg, p["ssm"], h)
    if cfg.cross_attention and enc_kv is not None:
        h = L.rmsnorm(x, p["xattn"]["norm"], cfg.norm_eps)
        x = x + L.cross_attention(cfg, p["xattn"], h, *enc_kv)
    if kind["moe"]:
        h = L.rmsnorm(x, p["moe"]["norm"], cfg.norm_eps)
        h, a, load = L.moe(cfg, p["moe"], h, bias)
        if cfg.sandwich_norm:
            h = L.rmsnorm(h, p["moe"]["post_norm"], cfg.norm_eps)
        x, aux = x + h, aux + a
    elif kind["mlp"]:
        x = _dense_mlp(cfg, p["mlp"], x)
    return x, aux, load


def _dense_mlp(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    h = L.rmsnorm(x, p["norm"], cfg.norm_eps)
    if cfg.mla:                          # DeepSeek block: mixed precision
        with jax.named_scope("dense_mlp"):
            return x + L.swiglu(cfg, p, h)
    h = L.mlp(cfg, p, h)
    if cfg.sandwich_norm:
        h = L.rmsnorm(h, p["post_norm"], cfg.norm_eps)
    return x + h


def _leading_dense(cfg: ModelConfig, params: Dict, x: jax.Array,
                   positions: jax.Array) -> jax.Array:
    """The dense layers before the scanned stack (MLA + SwiGLU)."""
    def body(x, p):
        h = L.rmsnorm(x, p["attn"]["norm"], cfg.norm_eps)
        x = x + L.mla(cfg, p["attn"], h, positions)
        return _dense_mlp(cfg, p["mlp"], x), None

    fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = lax.scan(fn, x, params["dense"])
    return x


def _embed(cfg: ModelConfig, params: Dict, tokens: jax.Array,
           image_embeds: Optional[jax.Array],
           position: Optional[jax.Array] = None) -> jax.Array:
    x = params["embed"][tokens]                      # (B,S,D) gather
    if cfg.scale_embedding:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.num_image_tokens and image_embeds is not None:
        x = lax.dynamic_update_slice(x, image_embeds.astype(x.dtype), (0, 0, 0))
    if cfg.rope_theta == 0:                          # absolute sinusoidal pos
        if position is not None:                     # decode: (B,) positions
            div = jnp.exp(-math.log(10_000.0)
                          * jnp.arange(0, cfg.d_model, 2, dtype=f32) / cfg.d_model)
            ang = position.astype(f32)[:, None] * div[None, :]
            pos = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
            x = x + pos[:, None, :].astype(x.dtype)
        else:
            pos = L.sinusoidal_embedding(x.shape[1], cfg.d_model).astype(x.dtype)
            x = x + pos[None]
    return shard(x, "batch", "seq", "embed")


def _unembed(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (x @ head.astype(x.dtype)).astype(f32)
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return shard(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# encoder (whisper)
# ---------------------------------------------------------------------------

def encode(cfg: ModelConfig, params: Dict, frames: jax.Array) -> jax.Array:
    """Bidirectional encoder over precomputed frame embeddings (B,F,D)."""
    enc = params["encoder"]
    x = frames + L.sinusoidal_embedding(frames.shape[1],
                                        cfg.d_model).astype(frames.dtype)[None]
    x = shard(x, "batch", "seq", "embed")
    pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])

    def body(x, p):
        h = L.rmsnorm(x, p["attn"]["norm"], cfg.norm_eps)
        x = x + L.attention(cfg, p["attn"], h, pos, causal=False)
        h = L.rmsnorm(x, p["mlp"]["norm"], cfg.norm_eps)
        x = x + L.mlp(cfg, p["mlp"], h)
        return x, None

    fn = jax.checkpoint(body) if cfg.remat else body
    if cfg.scan_layers:
        x, _ = lax.scan(fn, x, enc["layers"])
    else:
        for r in range(cfg.encoder_layers):
            x, _ = fn(x, jax.tree.map(lambda t, r=r: t[r], enc["layers"]))
    return L.rmsnorm(x, enc["final_norm"], cfg.norm_eps)


def encoder_kv(cfg: ModelConfig, params: Dict, enc_out: jax.Array):
    """Precompute stacked cross-attention K/V: (R, period?, B, F, K, hd).

    Cross-attn K/V depend only on encoder output; computing them once per
    request (not per decode step) is the enc-dec analogue of a KV cache.
    """
    B, F, _ = enc_out.shape
    K, hd = cfg.num_kv_heads, cfg.head_dim
    period = block_period(cfg)

    ks, vs = [], []
    for j in range(period):
        p = params["blocks"][f"blk{j}"]["xattn"]
        # einsum over the repeat dim: (R,D,KV) x (B,F,D) -> (R,B,F,KV)
        k = jnp.einsum("bfd,rde->rbfe", enc_out, p["wk"])
        v = jnp.einsum("bfd,rde->rbfe", enc_out, p["wv"])
        R = k.shape[0]
        ks.append(k.reshape(R, B, F, K, hd))
        vs.append(v.reshape(R, B, F, K, hd))
    return {"k": ks, "v": vs}


# ---------------------------------------------------------------------------
# train / prefill forward
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params: Dict, tokens: jax.Array, *,
            image_embeds: Optional[jax.Array] = None,
            encoder_frames: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits fp32 (B,S,V), moe_aux_loss)."""
    x, aux, _ = trunk(cfg, params, tokens, image_embeds=image_embeds,
                      encoder_frames=encoder_frames)
    return _unembed(cfg, params, x), aux / max(1, cfg.num_layers)


def trunk(cfg: ModelConfig, params: Dict, tokens: jax.Array, *,
          image_embeds: Optional[jax.Array] = None,
          encoder_frames: Optional[jax.Array] = None,
          router_bias: Optional[jax.Array] = None):
    """Embedding and every layer, before the final norm. Returns (hidden
    (B,S,D), summed MoE aux loss, MoE load (R, E) or None). ``router_bias``
    (R, E) is the router state's correction bias of each MoE layer."""
    B, S = tokens.shape
    period = block_period(cfg)
    x = _embed(cfg, params, tokens, image_embeds)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.first_dense_layers:
        x = _leading_dense(cfg, params, x, positions)

    enc_kv_stacked = None
    if cfg.encoder_layers:
        enc_out = encode(cfg, params, encoder_frames)
        enc_kv_stacked = encoder_kv(cfg, params, enc_out)

    kinds = [sublayer_kind(cfg, j) for j in range(period)]
    if router_bias is not None and period != 1:
        raise ValueError("a router bias needs an MoE layer in every position")

    def body(carry, xs):
        x, aux = carry
        blk_params, enc_kv, bias = xs
        load = None
        for j in range(period):
            ekv = None
            if enc_kv is not None:
                ekv = (enc_kv["k"][j], enc_kv["v"][j])
            x, aux, lj = _apply_sublayer(cfg, kinds[j], blk_params[f"blk{j}"],
                                         x, positions, aux, ekv, bias)
            load = lj if lj is not None else load
        return (x, aux), load

    fn = jax.checkpoint(body) if cfg.remat else body
    xs = (params["blocks"], enc_kv_stacked, router_bias)
    carry = (x, jnp.zeros((), f32))
    if cfg.scan_layers:
        (x, aux), load = lax.scan(fn, carry, xs)
    else:                                # unrolled (dry-run cost probes)
        loads = []
        for r in range(num_repeats(cfg)):
            carry, lr = fn(carry, jax.tree.map(lambda t, r=r: t[r], xs))
            loads.append(lr)
        x, aux = carry
        load = None if loads[0] is None else jnp.stack(loads)
    return x, aux, load


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------

def cache_defs(cfg: ModelConfig, batch: int, s_max: int) -> Dict:
    """ParamDef pytree for the decode cache (abstract-able for the dry-run).

    Attention sublayers carry (k,v) ring/full caches; SSM sublayers carry a
    conv window + the SSD state. Whisper additionally carries precomputed
    cross-attention K/V over the 1500 encoder frames.
    """
    R, period = num_repeats(cfg), block_period(cfg)
    K, hd = cfg.num_kv_heads, cfg.head_dim
    dt = cfg.dtype
    cache: Dict = {}
    for j in range(period):
        kind = sublayer_kind(cfg, j)
        c: Dict = {}
        if kind["attn"]:
            s_len = s_max
            if kind["local"] and cfg.swa_ring_buffer and cfg.sliding_window:
                s_len = min(s_max, cfg.sliding_window)
            axes = ("layer", "batch", "kv_seq", "kv_heads", None)
            cdt = "int8" if cfg.kv_cache_int8 else dt
            c["k"] = ParamDef((R, batch, s_len, K, hd), axes, "zeros", cdt)
            c["v"] = ParamDef((R, batch, s_len, K, hd), axes, "zeros", cdt)
            if cfg.kv_cache_int8:
                sax = ("layer", "batch", "kv_seq", "kv_heads")
                c["k_scale"] = ParamDef((R, batch, s_len, K), sax, "zeros", dt)
                c["v_scale"] = ParamDef((R, batch, s_len, K), sax, "zeros", dt)
        if kind["ssm"]:
            conv_dim = cfg.d_inner + 2 * cfg.ssm_state
            c["conv"] = ParamDef((R, batch, cfg.ssm_conv_width - 1, conv_dim),
                                 ("layer", "batch", None, "tensor"), "zeros", dt)
            c["ssm"] = ParamDef((R, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                                 cfg.ssm_state),
                                ("layer", "batch", "heads", None, None),
                                "zeros", "float32")
        if cfg.cross_attention:
            F = cfg.num_encoder_frames
            axes = ("layer", "batch", None, "kv_heads", None)
            c["xk"] = ParamDef((R, batch, F, K, hd), axes, "zeros", dt)
            c["xv"] = ParamDef((R, batch, F, K, hd), axes, "zeros", dt)
        cache[f"blk{j}"] = c
    return cache


def _decode_sublayer(cfg: ModelConfig, kind: Dict, p: Dict, c: Dict,
                     x: jax.Array, position: jax.Array):
    new_c: Dict = {}
    if kind["attn"]:
        h = L.rmsnorm(x, p["attn"]["norm"], cfg.norm_eps)
        ring = bool(kind["local"] and cfg.swa_ring_buffer and cfg.sliding_window
                    and c["k"].shape[1] < cfg.sliding_window + 1)
        scales = ((c["k_scale"], c["v_scale"]) if cfg.kv_cache_int8 else None)
        h, nk, nv, nsc = L.attention_decode(cfg, p["attn"], h, c["k"], c["v"],
                                            position, is_local=kind["local"],
                                            ring=ring, scales=scales)
        if cfg.sandwich_norm:
            h = L.rmsnorm(h, p["attn"]["post_norm"], cfg.norm_eps)
        x = x + h
        new_c["k"], new_c["v"] = nk, nv
        if cfg.kv_cache_int8:
            new_c["k_scale"], new_c["v_scale"] = nsc
    elif kind["ssm"]:
        h = L.rmsnorm(x, p["ssm"]["norm"], cfg.norm_eps)
        h, nconv, nssm = L.ssd_decode(cfg, p["ssm"], h, c["conv"], c["ssm"])
        x = x + h
        new_c["conv"], new_c["ssm"] = nconv, nssm
    if cfg.cross_attention:
        h = L.rmsnorm(x, p["xattn"]["norm"], cfg.norm_eps)
        x = x + L.cross_attention(cfg, p["xattn"], h, c["xk"], c["xv"])
        new_c["xk"], new_c["xv"] = c["xk"], c["xv"]
    if kind["moe"]:
        h = L.rmsnorm(x, p["moe"]["norm"], cfg.norm_eps)
        h, _, _ = L.moe(cfg, p["moe"], h)
        if cfg.sandwich_norm:
            h = L.rmsnorm(h, p["moe"]["post_norm"], cfg.norm_eps)
        x = x + h
    elif kind["mlp"]:
        h = L.rmsnorm(x, p["mlp"]["norm"], cfg.norm_eps)
        h = L.mlp(cfg, p["mlp"], h)
        if cfg.sandwich_norm:
            h = L.rmsnorm(h, p["mlp"]["post_norm"], cfg.norm_eps)
        x = x + h
    return x, new_c


def decode_step(cfg: ModelConfig, params: Dict, cache: Dict,
                tokens: jax.Array, position: jax.Array
                ) -> Tuple[jax.Array, Dict]:
    """One decode step. tokens:(B,1) int32; position:(B,) int32.

    Returns (logits fp32 (B,V), new cache). The cache is scanned alongside
    the stacked block params so HLO stays O(1) in depth.
    """
    period = block_period(cfg)
    x = _embed(cfg, params, tokens, None, position=position)
    kinds = [sublayer_kind(cfg, j) for j in range(period)]

    def body(x, xs):
        blk_params, blk_cache = xs
        new_cache = {}
        for j in range(period):
            x, nc = _decode_sublayer(cfg, kinds[j], blk_params[f"blk{j}"],
                                     blk_cache[f"blk{j}"], x, position)
            new_cache[f"blk{j}"] = nc
        return x, new_cache

    if cfg.scan_layers:
        x, new_cache = lax.scan(body, x, (params["blocks"], cache))
    else:                                # unrolled (dry-run cost probes)
        outs = []
        for r in range(num_repeats(cfg)):
            x, nc = body(x, jax.tree.map(lambda t, r=r: t[r],
                                         (params["blocks"], cache)))
            outs.append(nc)
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    logits = _unembed(cfg, params, x)
    return logits[:, -1, :], new_cache


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def xent_loss(logits: jax.Array, labels: jax.Array,
              mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross entropy; logits fp32 (B,S,V), labels (B,S)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def loss_and_load(cfg: ModelConfig, params: Dict, batch: Dict,
                  router_bias: Optional[jax.Array] = None,
                  aux_weight: float = 0.01) -> Tuple[jax.Array, Tuple]:
    """Training loss of one batch: (total, (MoE load (R, E) or None,
    metrics)). A configuration with a vision tower takes ``pixels`` (B, H,
    W, 3) and ``tokens`` and is scored on its text (``text_xent``); others
    take ``labels`` and an optional ``mask``. With sigmoid routing the aux
    term is the sequence-wise balance loss summed over the MoE layers,
    times ``seq_aux_weight``."""
    image_embeds = batch.get("image_embeds")
    if cfg.vision_layers:
        image_embeds = vision.image_embeds(cfg, params, batch["pixels"])
    x, aux, load = trunk(cfg, params, batch["tokens"], image_embeds=image_embeds,
                         encoder_frames=batch.get("encoder_frames"),
                         router_bias=router_bias)
    if cfg.vision_layers:
        loss = text_xent(cfg, params, x, batch["tokens"])
    else:
        loss = xent_loss(_unembed(cfg, params, x), batch["labels"],
                         batch.get("mask"))
    if cfg.router_scoring == "sigmoid":
        total = loss + cfg.seq_aux_weight * aux
    else:
        aux = aux / max(1, cfg.num_layers)
        total = loss + aux_weight * aux
    return total, (load, {"xent": loss, "moe_aux": aux})


HEAD_CHUNKS = 4      # the text head's logits, by chunks of the batch


def text_xent(cfg: ModelConfig, params: Dict, x: jax.Array,
              tokens: jax.Array) -> jax.Array:
    """Mean next-token cross entropy over the text after the image: the
    hidden state at position t predicts token t + 1, from the last image
    position on, so the image positions carry no loss. Computed by chunks
    of sequences, each rematerialised, so one chunk's logits live at a
    time."""
    n = cfg.num_image_tokens
    h, labels = x[:, n - 1:-1], tokens[:, n:]
    step = h.shape[0] // math.gcd(HEAD_CHUNKS, h.shape[0])

    def chunk(norm_w, head, hc, lc):
        hc = L.rmsnorm(hc, norm_w, cfg.norm_eps)
        logits = L.mm(cfg, hc, head)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    with jax.named_scope("lm_head"):
        total = sum(jax.checkpoint(chunk)(params["final_norm"], params["head"],
                                          h[i:i + step], labels[i:i + step])
                    for i in range(0, h.shape[0], step))
    return total / labels.size


# ---------------------------------------------------------------------------
# router state (sigmoid routing): the correction bias and the routed count
# ---------------------------------------------------------------------------

def init_router_state(cfg: ModelConfig) -> Dict:
    """The training step's state: each MoE layer's correction bias (R, E),
    a running count of the token-slots routed to each held expert (R,
    held), and of the layer-steps whose held slots exceeded the routed
    experts' capacity (``layers.routed_capacity``), which took the
    full-size path (R,). Empty for a model without sigmoid routing."""
    if cfg.router_scoring != "sigmoid":
        return {}
    R = num_repeats(cfg)
    return {"bias": jnp.zeros((R, cfg.num_experts), f32),
            "routed": jnp.zeros((R, cfg.held_experts), jnp.int32),
            "fallbacks": jnp.zeros((R,), jnp.int32)}


def update_router_state(cfg: ModelConfig, state: Dict,
                        load: Optional[jax.Array]) -> Dict:
    """DeepSeek-V3's bias rule, b_i += gamma * sign(mean load - load_i), on
    the load of every expert; the held experts' slots join the count, and
    a layer whose held slots exceed the capacity of its slots counts a
    fallback."""
    if not state:
        return state
    mean = jnp.mean(load, axis=-1, keepdims=True)
    lo = cfg.expert_offset
    held = load[:, lo:lo + cfg.held_experts].astype(jnp.int32)
    cap = L.routed_capacity(cfg, jnp.sum(load, axis=-1).astype(jnp.int32))
    return {"bias": state["bias"] + cfg.bias_update_rate * jnp.sign(mean - load),
            "routed": state["routed"] + held,
            "fallbacks": state["fallbacks"]
            + (jnp.sum(held, axis=-1) > cap).astype(jnp.int32)}
