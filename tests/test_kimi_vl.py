"""Kimi-VL-A3B at its CPU size against the plain reference
(``models/ref_kimi_vl.py``), on seeded random weights: MLA, the DeepSeek-V3
MoE (dropless under full imbalance, and the expert share adding up to the
uncut layer), the vision tower and projector, the whole training step, and
the shared trainer loop's spans and prefetch on the VLM step."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs import get_config, get_smoke
from repro.models import layers as L
from repro.models import lm, vision
from repro.models import ref_kimi_vl as ref
from repro.models.params import ParamDef, count, materialize
from repro.train import loop, optim

CFG = get_smoke("kimi-vl-a3b")
B, S = 2, 16


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _params(cfg=CFG, seed=0):
    return materialize(lm.param_defs(cfg), jax.random.key(seed))


def _batch(cfg=CFG, seed=1, b=B):
    rng = np.random.default_rng(seed)
    return {"pixels": jnp.asarray(rng.normal(size=(b, cfg.image_hw, cfg.image_hw, 3)),
                                  jnp.float32),
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, S)), jnp.int32)}


def _x(seed=2, d=CFG.d_model):
    return jax.random.normal(jax.random.key(seed), (B, S, d), jnp.float32)


def _close(a, b, tol=2e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        assert x.shape == y.shape
        assert np.linalg.norm(x - y) <= tol * max(np.linalg.norm(y), 1e-12)


def _layer(params, key):
    return jax.tree.map(lambda t: t[0], params["blocks"]["blk0"][key])


def test_published_widths_and_memory_reckoning():
    cfg = get_config("kimi-vl-a3b")
    defs = lm.param_defs(cfg)
    n = count(defs)
    assert 660e6 < n < 670e6                   # 665 M: LM 568 M, vision 97 M
    assert 95e6 < count({"v": defs["vision"], "p": defs["projector"]}) < 99e6
    moe = defs["blocks"]["blk0"]["moe"]
    assert moe["router"].shape == (4, 2048, 64)
    assert moe["we_gate"].shape == (4, 8, 2048, 1408)
    assert moe["shared"]["wi_gate"].shape == (4, 2048, 2816)
    assert defs["head"].shape == (2048, 20_480)
    assert all(d.dtype == "float32" for d in jax.tree.leaves(
        defs, is_leaf=lambda d: isinstance(d, ParamDef)))


@pytest.mark.parametrize("part", ["forward", "gradient"])
def test_mla_matches_reference(part):
    p = _layer(_params(), "attn")
    x = _x()
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def prog(p, x):
        return L.mla(CFG, p, x, pos, q_block=4)

    def plain(p, x):
        return ref.mla(CFG, p, x)

    if part == "forward":
        _close(prog(p, x), plain(p, x))
    else:
        loss = lambda f: (lambda p, x: jnp.sum(jnp.sin(f(p, x))))
        _close(jax.grad(loss(prog), (0, 1))(p, x), jax.grad(loss(plain), (0, 1))(p, x))


def test_moe_drops_nothing_when_every_token_picks_the_same_experts():
    """A bias that forces every token onto experts 0..K-1 puts all T*K
    slots on held experts, T on each: far over any capacity."""
    p = _layer(_params(), "moe")
    x = _x()
    bias = jnp.where(jnp.arange(CFG.num_experts) < CFG.experts_per_token, 100.0, 0.0)
    y, aux, load = L.moe(CFG, p, x, bias)
    y_ref, aux_ref, load_ref = ref.moe(CFG, p, x, bias)
    assert float(load[0]) == B * S and float(load[:CFG.experts_per_token].sum()) == (
        B * S * CFG.experts_per_token)
    _close(load, load_ref, 0)
    _close(y, y_ref)
    _close(aux, aux_ref)
    # the capacity-bounded path would have dropped slots on expert 0
    assert B * S > B * S * CFG.experts_per_token * CFG.capacity_factor / CFG.num_experts


def _undefined_past_groups(real):
    """``lax.ragged_dot`` as the TPU leaves it: the rows past the groups
    undefined (NaN here) in the output and in the gradient for the rows."""
    def ragged_dot(a, w, sizes, preferred_element_type=None, **kw):
        live = (jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None]
        dot = lambda a, w: real(a, w, sizes,
                                preferred_element_type=preferred_element_type)

        @jax.custom_vjp
        def f(a, w):
            return jnp.where(live, dot(a, w), jnp.nan)

        def bwd(res, g):
            da, dw = jax.vjp(dot, *res)[1](g)
            return jnp.where(live, da, jnp.nan), dw

        f.defvjp(lambda a, w: (f(a, w), (a, w)), bwd)
        return f(a, w)
    return ragged_dot


def test_moe_ignores_rows_past_the_groups(monkeypatch):
    """Output and gradients match the reference, and stay finite, when the
    grouped matmul leaves the rows past its groups undefined."""
    monkeypatch.setattr(L.lax, "ragged_dot", _undefined_past_groups(L.lax.ragged_dot))
    p = _layer(_params(), "moe")
    x = _x()
    bias = jnp.zeros((CFG.num_experts,))

    def loss(f):
        return lambda p, x: jnp.sum(jnp.sin(f(CFG, p, x, bias)[0]))

    got = jax.value_and_grad(loss(L.moe), (0, 1))(p, x)
    want = jax.value_and_grad(loss(ref.moe), (0, 1))(p, x)
    assert all(np.isfinite(np.asarray(t)).all() for t in jax.tree.leaves(got))
    _close(got, want)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Each chip's routed part, over every share of the experts, with the
    shared experts counted once, is the reference layer with all experts."""
    full = dataclasses.replace(CFG, experts_held=CFG.num_experts)
    pf = _layer(_params(full), "moe")
    x = _x()
    bias = jax.random.normal(jax.random.key(5), (CFG.num_experts,)) * 0.05
    want, _, _ = ref.moe(full, pf, x, bias)
    G = CFG.experts_held
    shares = []
    for lo in range(0, CFG.num_experts, G):
        cfg = dataclasses.replace(CFG, expert_offset=lo, num_shared_experts=0)
        p = {k: pf[k][lo:lo + G] if k.startswith("we_") else pf[k] for k in pf}
        y, _, _ = L.moe(cfg, p, x, bias)
        shares.append(y)
    shared = L.shared_experts(CFG, pf, x.reshape(-1, CFG.d_model)).reshape(x.shape)
    _close(sum(shares) + shared, want)


def test_vision_tower_and_projector_match_reference():
    params = _params()
    pixels = _batch()["pixels"]
    got = vision.image_embeds(CFG, params, pixels)
    want = ref.projector(CFG, params["projector"],
                         ref.vision(CFG, params["vision"], pixels))
    assert got.shape == (B, CFG.num_image_tokens, CFG.d_model)
    _close(got, want)


def test_bicubic_resize_is_pytorch_s():
    """Downsampling by 2 at half-pixel centres takes taps -1, 0, 1, 2 with
    weights -0.09375, 0.59375, 0.59375, -0.09375; the same size is exact."""
    m = vision.bicubic_matrix(8, 4)
    np.testing.assert_allclose(m[1, 1:5], [-0.09375, 0.59375, 0.59375, -0.09375])
    np.testing.assert_allclose(m.sum(1), 1.0)
    np.testing.assert_allclose(vision.bicubic_matrix(5, 5), np.eye(5), atol=1e-12)


def test_train_step_matches_reference():
    """One step of ``make_lm_step``: loss, clipped gradient (AdamW's first
    moment over 0.1), the parameters after it, and the router state."""
    params, batch = _params(), _batch()
    state = lm.init_router_state(CFG)
    lr_fn = lambda step: jnp.float32(1e-3)
    step = loop.make_lm_step(CFG, lr_fn)
    p_ref = jax.tree.map(jnp.copy, params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    want = ref.train_step(CFG, p_ref, ref.initial_state(CFG), zeros, zeros, 0,
                          batch, 1e-3)
    new_p, new_s, opt, metrics = step(params, state, optim.adamw_init(params),
                                      batch, jnp.asarray(0))
    np.testing.assert_allclose(float(metrics["loss"]), float(want[4]), rtol=1e-5)
    _close(jax.tree.map(lambda m: m / 0.1, opt.m), want[5], 1e-4)
    _close(new_p, want[0], 1e-5)
    _close(new_s["bias"], want[1]["bias"], 0)
    _close(new_s["routed"], want[1]["routed"], 0)
    assert np.array_equal(np.asarray(metrics["routed_slots"]),
                          np.asarray(new_s["routed"]))


def test_run_lm_training_records_the_loop_spans_and_prefetches():
    steps = 4

    def batches():
        for i in range(steps):
            yield {k: np.asarray(v) for k, v in _batch(seed=10 + i).items()}, i

    before = spans.RECORDER.counters().get(loop.PREFETCHED, 0)
    lo = time.time_ns()
    res = loop.run_lm_training(CFG, _params(), lm.init_router_state(CFG),
                               batches(), steps=steps,
                               hooks=loop.TrainHooks(log_every=0))
    evs = spans.RECORDER.events(lo, time.time_ns())
    assert len(res.losses) == steps and all(np.isfinite(res.losses))
    assert spans.RECORDER.counters()[loop.PREFETCHED] - before == steps - 1
    names = {n for _, _, n, _ in evs}
    assert names == {"train.step", "train.next", "train.put", "train.dispatch",
                     "train.fetch", "train.hooks"}
    assert [k for _, _, n, k in evs if n == "train.step"] == list(range(steps))
    routed = np.asarray(res.extras["state"]["routed"])
    # every step routes T*K slots a layer, some of them to the held experts
    assert 0 < routed.sum(1).max() <= steps * B * S * CFG.experts_per_token


def test_materialize_fan_in_is_the_contracted_axis():
    d = ParamDef((3, 5, 256, 64), ("layer", "expert", "fsdp", "tensor"), "scaled",
                 "float32")
    w = np.asarray(materialize({"w": d}, jax.random.key(0))["w"])
    assert abs(w.std() - 1 / np.sqrt(256)) < 0.02 / np.sqrt(256)
