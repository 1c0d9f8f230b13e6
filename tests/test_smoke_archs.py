"""Per-assigned-architecture smoke tests (assignment deliverable f).

Every arch instantiates its REDUCED config and runs one forward + one train
step on CPU, asserting output shapes and finiteness; decode-capable archs
additionally run one serve step.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import LM_ARCHS, get_smoke
from repro.models import lm
from repro.models.params import count, materialize
from repro.train import optim


def _batch(cfg, B=2, S=32):
    tok = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tok, "labels": tok}
    if cfg.num_image_tokens:
        batch["image_embeds"] = jnp.zeros(
            (B, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.encoder_layers:
        batch["encoder_frames"] = jax.random.normal(
            jax.random.key(2), (B, cfg.num_encoder_frames, cfg.d_model)
        ).astype(jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_smoke(arch)
    params = materialize(lm.param_defs(cfg), jax.random.key(0))
    assert count(lm.param_defs(cfg)) < 5_000_000, "smoke config too large"
    B, S = 2, 32
    batch = _batch(cfg, B, S)
    logits, aux = lm.forward(cfg, params, batch["tokens"],
                             image_embeds=batch.get("image_embeds"),
                             encoder_frames=batch.get("encoder_frames"))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_one_train_step_reduces_loss_direction(arch):
    cfg = get_smoke(arch)
    params = materialize(lm.param_defs(cfg), jax.random.key(0))
    opt = optim.adamw_init(params)
    batch = _batch(cfg)

    @jax.jit
    def step(params, opt):
        (loss, _), grads = jax.value_and_grad(
            lm.loss_and_load, has_aux=True, argnums=1)(cfg, params, batch)
        grads, _ = optim.clip_by_global_norm(grads, 1.0)
        params, opt = optim.adamw_update(grads, opt, params, lr=1e-3)
        return params, opt, loss

    params, opt, l0 = step(params, opt)
    assert bool(jnp.isfinite(l0))
    # same batch again: loss must drop after one optimizer step
    _, _, l1 = step(params, opt)
    assert float(l1) < float(l0)


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_decode_step(arch):
    cfg = get_smoke(arch)
    params = materialize(lm.param_defs(cfg), jax.random.key(0))
    B, S_max = 2, 16
    cache = jax.tree.map(jnp.zeros_like,
                         materialize(lm.cache_defs(cfg, B, S_max),
                                     jax.random.key(1)))
    logits, cache2 = lm.decode_step(cfg, params, cache,
                                    jnp.ones((B, 1), jnp.int32),
                                    jnp.zeros((B,), jnp.int32))
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


def test_prefill_decode_consistency_dense():
    cfg = get_smoke("llama3.2-1b")
    params = materialize(lm.param_defs(cfg), jax.random.key(0))
    B, S = 1, 8
    tok = jax.random.randint(jax.random.key(3), (B, S), 0, cfg.vocab_size)
    full, _ = lm.forward(cfg, params, tok)
    cache = jax.tree.map(jnp.zeros_like,
                         materialize(lm.cache_defs(cfg, B, S),
                                     jax.random.key(1)))
    for t in range(S):
        lg, cache = lm.decode_step(cfg, params, cache, tok[:, t:t + 1],
                                   jnp.array([t]))
        assert float(jnp.max(jnp.abs(lg - full[:, t, :]))) < 1e-3


def test_prefill_decode_consistency_hybrid():
    # capacity for every slot: the prefill's 8 tokens may all pick one
    # expert, which one decoded token never overflows (and the dropped
    # slots, not the SSD forms, would then be what the check sees)
    cfg = get_smoke("jamba-1.5-large-398b")
    cfg = dataclasses.replace(cfg, capacity_factor=cfg.num_experts
                              / cfg.experts_per_token)
    params = materialize(lm.param_defs(cfg), jax.random.key(0))
    B, S = 1, 8
    tok = jax.random.randint(jax.random.key(3), (B, S), 0, cfg.vocab_size)
    full, _ = lm.forward(cfg, params, tok)
    cache = jax.tree.map(jnp.zeros_like,
                         materialize(lm.cache_defs(cfg, B, S),
                                     jax.random.key(1)))
    for t in range(S):
        lg, cache = lm.decode_step(cfg, params, cache, tok[:, t:t + 1],
                                   jnp.array([t]))
    # bf16 SSD accumulation differs slightly between chunked & stepwise forms
    # (~0.16 max logit gap on jax 0.4.37 CPU)
    assert float(jnp.max(jnp.abs(lg - full[:, -1, :]))) < 0.20


def _moe_slot_by_slot(cfg, p, xf):
    """The capacity-bounded MoE written out: slots in token order, an
    expert's slots past its capacity dropped."""
    import math
    T, (E, k) = xf.shape[0], (cfg.num_experts, cfg.experts_per_token)
    C = max(1, math.ceil(T * k * cfg.capacity_factor / E))
    w = {n: p[n].astype(jnp.float32) for n in ("router", "we_gate", "we_up", "we_down")}
    gates, eidx = jax.lax.top_k(jax.nn.softmax(xf @ w["router"], axis=-1), k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    seen = [0] * E
    rows = []
    for t in range(T):
        y = jnp.zeros(xf.shape[1])
        for j in range(k):
            e = int(eidx[t, j])
            if seen[e] < C:
                h = jax.nn.silu(xf[t] @ w["we_gate"][e]) * (xf[t] @ w["we_up"][e])
                y = y + gates[t, j] * (h @ w["we_down"][e])
            seen[e] += 1
        rows.append(y)
    return jnp.stack(rows), C


@pytest.mark.parametrize("tokens", ["repeated", "random"])
def test_hybrid_moe_drops_the_slots_past_capacity(tokens):
    """Jamba's MoE at its own capacity factor keeps each expert's first C
    slots in token order and drops the rest: eight copies of one token all
    pick the same two experts, so the last 8 - C get nothing."""
    from repro.models import layers
    cfg = get_smoke("jamba-1.5-large-398b")
    assert cfg.act == "silu" and cfg.router_scoring != "sigmoid"
    p = materialize(layers.moe_param_defs(cfg), jax.random.key(0))
    if tokens == "repeated":
        x = jnp.tile(jax.random.normal(jax.random.key(1), (1, 1, cfg.d_model)),
                     (1, 8, 1))
    else:
        x = jax.random.normal(jax.random.key(1), (1, 8, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, _, _ = layers.moe(cfg, p, x)
        want, C = _moe_slot_by_slot(cfg, p, x[0])
    y = y[0].astype(jnp.float32)
    assert float(jnp.max(jnp.abs(y - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))
    if tokens == "repeated":
        assert C < 8
        assert float(jnp.min(jnp.abs(y[:C]).max(axis=-1))) > 0
        assert float(jnp.max(jnp.abs(y[C:]))) == 0.0


def test_scan_vs_unrolled_forward_match():
    """scan and unrolled stacks are the same math; bf16 accumulation order
    differs under different XLA fusions, so compare semantically."""
    import dataclasses
    import numpy as np
    cfg = get_smoke("gemma2-9b")
    params = materialize(lm.param_defs(cfg), jax.random.key(0))
    tok = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    a, _ = lm.forward(cfg, params, tok)
    b, _ = lm.forward(dataclasses.replace(cfg, scan_layers=False), params, tok)
    assert float(jnp.mean(jnp.abs(a - b))) < 0.05
    agree = float(jnp.mean(jnp.argmax(a, -1) == jnp.argmax(b, -1)))
    assert agree >= 0.9
