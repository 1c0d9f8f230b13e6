"""Kimi-VL-A3B at its CPU size against the plain reference
(``models/ref_kimi_vl.py``), on seeded random weights: MLA, the DeepSeek-V3
MoE (dropless under full imbalance, the routed experts' capacity path and
its full-size fallback, and the expert share adding up to the uncut
layer), the vision tower and projector, the whole training step, and the
shared trainer loop's spans and prefetch on the VLM step."""
import dataclasses
import functools
import re
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
# A router over 16 experts, 4 held, 2 a token, on 512 tokens: the routed
# experts' capacity is 512 of the 1,024 token-slots.
CAP = dataclasses.replace(CFG, num_experts=16, experts_held=4, experts_per_token=2)
CAP_S = 256
# experts that a router bias of 100 forces onto every token: typical
# routing takes the capacity path, the others hold all, exactly C, or over
# C (with rows past the groups) of the slots on held experts
FORCED = {"typical": (), "all_held": (0, 1), "exactly_c": (0, 8), "over_c": (0,)}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _params(cfg=CFG, seed=0):
    return materialize(lm.param_defs(cfg), jax.random.key(seed))


def _batch(cfg=CFG, seed=1, b=B, s=S):
    rng = np.random.default_rng(seed)
    return {"pixels": jnp.asarray(rng.normal(size=(b, cfg.image_hw, cfg.image_hw, 3)),
                                  jnp.float32),
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)}


def _x(seed=2, d=CFG.d_model, s=S):
    return jax.random.normal(jax.random.key(seed), (B, s, d), jnp.float32)


def _forced_bias(cfg, case):
    return jnp.zeros((cfg.num_experts,)).at[jnp.asarray(FORCED[case], int)].set(100.0)


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
        def dot(a, w, sizes):
            return real(a, w, sizes, preferred_element_type=preferred_element_type)

        def live(a, sizes):
            return (jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None]

        @jax.custom_vjp
        def f(a, w, sizes):
            return jnp.where(live(a, sizes), dot(a, w, sizes), jnp.nan)

        def bwd(res, g):
            a, w, sizes = res
            da, dw = jax.vjp(lambda a, w: dot(a, w, sizes), a, w)[1](g)
            return jnp.where(live(a, sizes), da, jnp.nan), dw, None

        f.defvjp(lambda a, w, sizes: (f(a, w, sizes), (a, w, sizes)), bwd)
        return f(a, w, sizes)
    return ragged_dot


@pytest.mark.parametrize("path", ["full_size", "capacity", "fallback"])
def test_moe_ignores_rows_past_the_groups(monkeypatch, path):
    """Output and gradients match the reference, and stay finite, when the
    grouped matmul leaves the rows past its groups undefined: on the
    full-size path where the capacity is every token-slot, on the capacity
    path, and on the fallback from it."""
    monkeypatch.setattr(L.lax, "ragged_dot", _undefined_past_groups(L.lax.ragged_dot))
    cfg, s, case = {"full_size": (CFG, S, "typical"),
                    "capacity": (CAP, CAP_S, "typical"),
                    "fallback": (CAP, CAP_S, "over_c")}[path]
    p = _layer(_params(cfg), "moe")
    x = _x(d=cfg.d_model, s=s)
    bias = _forced_bias(cfg, case)
    held = ref.moe(cfg, p, x, bias)[2][:cfg.held_experts].sum()
    C = L.routed_capacity(cfg, B * s * cfg.experts_per_token)
    assert (C == B * s * cfg.experts_per_token) == (path == "full_size")
    assert (held > C) == (path == "fallback") and held < B * s * cfg.experts_per_token

    def loss(f):
        return lambda p, x: jnp.sum(jnp.sin(f(cfg, p, x, bias)[0]))

    got = jax.value_and_grad(loss(L.moe), (0, 1))(p, x)
    want = jax.value_and_grad(loss(ref.moe), (0, 1))(p, x)
    assert all(np.isfinite(np.asarray(t)).all() for t in jax.tree.leaves(got))
    _close(got, want)


@pytest.mark.parametrize("case", ["typical", "all_held", "exactly_c"])
def test_routed_capacity_path_matches_full_size_and_reference(monkeypatch, case):
    """Where the capacity is half the token-slots, the routed experts give
    the full-size path's output and gradients (for the tokens, the weights
    and the experts' weights) whether the held slots fit (typical routing,
    exactly C) or not (every slot held: the fallback, nothing dropped), and
    the layer matches the reference."""
    p = _layer(_params(CAP), "moe")
    x = _x(d=CAP.d_model, s=CAP_S)
    bias = _forced_bias(CAP, case)
    xf = x.reshape(-1, CAP.d_model)
    _, eidx, w = ref.route(CAP, p, xf, bias)
    T, K = eidx.shape
    C = L.routed_capacity(CAP, T * K)
    held = int(jnp.sum((eidx >= 0) & (eidx < CAP.held_experts)))
    assert C == T * K // 2
    assert {"typical": held < C, "all_held": held == T * K,
            "exactly_c": held == C}[case]

    we = {k: v for k, v in p.items() if k.startswith("we_")}

    def routed(we, xf, w):
        return jnp.sum(jnp.sin(L.routed_experts(CAP, we, xf, eidx, w)))

    got = jax.value_and_grad(routed, (0, 1, 2))(we, xf, w)
    with monkeypatch.context() as m:      # capacity at every slot: full size alone
        m.setattr(L, "routed_capacity", lambda cfg, slots: slots)
        want = jax.value_and_grad(routed, (0, 1, 2))(we, xf, w)
    _close(got, want, 1e-6)

    def loss(f):
        return lambda p, x: jnp.sum(jnp.sin(f(CAP, p, x, bias)[0]))

    _close(jax.value_and_grad(loss(L.moe), (0, 1))(p, x),
           jax.value_and_grad(loss(ref.moe), (0, 1))(p, x))


_TENSOR = re.compile(r"tensor<((?:\d+x)+)[a-z]")


def _row_counts(hlo_text):
    """The leading dimension of every array of rank two or more."""
    return {int(m.group(1).split("x")[0]) for m in _TENSOR.finditer(hlo_text)
            if m.group(1).count("x") >= 2}


def _case_branches(hlo_text):
    """The text of each branch of every ``stablehlo.case`` (a conditional),
    in branch order: index 0 is the false branch, 1 the true one."""
    cases = []
    for m in re.finditer(r'"stablehlo\.case"\([^)]*\) \(', hlo_text):
        branches, depth, i = [], 0, m.end()
        while depth or hlo_text[i] != ")":
            if hlo_text[i] == "{":
                depth += 1
                start = i if depth == 1 else start
            elif hlo_text[i] == "}":
                depth -= 1
                if not depth:
                    branches.append(hlo_text[start:i + 1])
            i += 1
        cases.append(branches)
    return cases


def test_capacity_path_holds_no_array_of_every_token_slot():
    """In the lowered forward and backward of ``routed_experts``, each
    conditional's capacity branch holds no (T*K, ...) array; its full-size
    branch does."""
    p = _layer(_params(CAP), "moe")
    we = {k: v for k, v in p.items() if k.startswith("we_")}
    xf = _x(d=CAP.d_model, s=CAP_S).reshape(-1, CAP.d_model)
    _, eidx, w = ref.route(CAP, p, xf, None)
    T, K = eidx.shape
    C = L.routed_capacity(CAP, T * K)

    def f(we, xf, w):
        return jnp.sum(L.routed_experts(CAP, we, xf, eidx, w))

    text = jax.jit(jax.value_and_grad(f, (0, 1, 2))).lower(we, xf, w).as_text()
    cases = _case_branches(text)
    assert len(cases) == 2                       # the forward and the backward
    for full, within in cases:
        assert T * K in _row_counts(full)
        assert C in _row_counts(within) and T * K not in _row_counts(within)


def test_routed_conditional_lies_outside_the_scope():
    """The conditional between the capacity path and the full-size path
    spans the branch it runs on the device, so it stays outside the
    ``moe.routed`` scope that the ops of both paths carry: each op counts
    once in the stage's time."""
    p = _layer(_params(CAP), "moe")
    x = _x(d=CAP.d_model, s=CAP_S)
    loss = lambda p, x: jnp.sum(L.moe(CAP, p, x, None)[0])
    step = jax.jit(jax.grad(jax.checkpoint(loss), (0, 1)))
    hlo = step.lower(p, x).compile().as_text()
    op_name = re.compile(r'op_name="([^"]*)"')
    conds = [op_name.search(ln).group(1) for ln in hlo.splitlines()
             if " conditional(" in ln]
    # the branches' ops, less the checkpoint's own (``remat2``)
    inside = [n for n in op_name.findall(hlo)
              if "/cond/branch_" in n and not n.endswith("/remat2")]
    assert conds and not any("moe.routed" in n for n in conds)
    assert inside and all("moe.routed" in n for n in inside)


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
    assert np.array_equal(np.asarray(metrics["routed_fallbacks"]),
                          np.asarray(new_s["fallbacks"]))


@functools.lru_cache(maxsize=1)
def _capacity_step():
    return loop.make_lm_step(CAP, lambda step: jnp.float32(1e-3))


@pytest.mark.parametrize("case", ["typical", "exactly_c", "all_held"])
def test_router_state_counts_full_size_fallbacks(case):
    """Each MoE layer counts the layer-steps whose held slots exceed the
    routed experts' capacity: none under typical routing or at exactly C
    held slots, one a step in each layer where every slot is held; the
    metrics carry the count."""
    params, batch = _params(CAP), _batch(CAP, s=CAP_S)
    state = lm.init_router_state(CAP)
    state["bias"] = jnp.broadcast_to(_forced_bias(CAP, case), state["bias"].shape)
    opt = optim.adamw_init(params)
    for i in range(2):
        params, state, opt, metrics = _capacity_step()(params, state, opt, batch,
                                                       jnp.asarray(i))
    want = {"typical": 0, "exactly_c": 0, "all_held": 2}[case]
    assert np.array_equal(np.asarray(metrics["routed_fallbacks"]),
                          np.full(lm.num_repeats(CAP), want))
    assert np.array_equal(np.asarray(state["fallbacks"]),
                          np.asarray(metrics["routed_fallbacks"]))
    assert np.isfinite(float(metrics["loss"]))


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
