"""The plain reference against the program's own DetNet/EDSNet, on the CPU at
reduced width: same parameter tree, same forward in train and eval mode,
same training step. On the CPU both compute in float32,
so they agree to float32 round-off."""
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
import generate
import reference
from harness import Run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC = {"detnet": "train_b256", "edsnet": "train_b16"}


def load(name, small):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(BENCH, "traffic", f"{TRAFFIC[name]}.json")) as fh:
        tr = json.load(fh)
    return small(cfg, tr)


def program_config(cfg):
    return Run(cell="t", cfg=cfg, traffic={}, limits={}, seed=0, seconds=0,
               trace=False, t_start=0.0, counter=None).program_config()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def shapes_of(tree):
    return jax.tree.map(lambda a: tuple(a.shape), tree)


@pytest.fixture(params=["detnet", "edsnet"])
def net(request, small):
    cfg, tr = load(request.param, small)
    params = jax.jit(partial(reference.init_params, cfg))(jax.random.key(1))
    batch = generate.batch(cfg, 5, 0, 4)
    return cfg, tr, params, batch


@pytest.mark.parametrize("name", ["detnet", "edsnet"])
def test_param_tree_matches_program_at_full_width(name):
    from repro.models import xr
    from repro.models.params import abstract
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as fh:
        cfg = json.load(fh)
    pdefs, sdefs = xr.param_defs(program_config(cfg))
    want_p = jax.tree.map(lambda s: tuple(s.shape), abstract(pdefs))
    want_s = jax.tree.map(lambda s: tuple(s.shape), abstract(sdefs))
    got_p, got_s = reference.param_shapes(cfg)
    assert got_p == want_p
    assert got_s == want_s


@pytest.mark.parametrize("train", [True, False])
def test_forward_matches_program(net, train):
    from repro.models import xr
    cfg, _, params, batch = net
    bn = reference.init_bn_state(cfg)
    bn = jax.tree.map(lambda a: a + 0.1, bn)
    x = jnp.asarray(batch["image"])
    want, want_state = xr.forward(program_config(cfg), params, bn, x, train=train)
    with jax.default_matmul_precision("highest"):
        got, stats = reference.forward(cfg, params, bn, x, train=train)
    assert set(got) == set(want)
    # float32 round-off, grown by BatchNorm over 4 rows at 1x1 in train mode
    for k in want:
        assert rel(got[k], want[k]) < 1e-4, k
    if train:
        upd = reference.running_stats(stats, bn)
        for n in want_state:
            for s in ("mean", "var"):
                assert rel(upd[n][s], want_state[n][s]) < 1e-5, (n, s)


def test_train_step_matches_program(net):
    from repro.models import xr
    from repro.train import loop, optim
    cfg, tr, params, batch = net
    loss_fn = {"circle": xr.circle_loss, "dice": xr.dice_loss}[cfg["loss"]]
    lr = 1e-3
    step = loop.make_xr_step(program_config(cfg), loss_fn, lambda s: lr + 0 * s)
    b = {k: jnp.asarray(v) for k, v in batch.items()}
    p0 = jax.tree.map(jnp.copy, params)
    opt = optim.adamw_init(params)
    bn = reference.init_bn_state(cfg)
    new_p, _, new_opt, metrics = step(params, bn, opt, b, jnp.asarray(0))
    m = jax.tree.map(jnp.zeros_like, p0)
    with jax.default_matmul_precision("highest"):
        ref_p, ref_m, _, loss, grads = reference.train_step(
            cfg, p0, m, m, b, 1, lr)
    assert abs(float(loss) - float(metrics["loss"])) < 1e-5 * abs(float(loss))
    assert compare.worst_leaf_gap(new_opt.m, ref_m) < 1e-3
    # Adam's first step moves each element by about lr * sign(g): elements
    # whose gradient is round-off, and whole leaves that are, move by chance
    keep = compare.moving_leaves(grads)
    change = lambda p: jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                                    p, p0)
    assert compare.worst_leaf_gap(change(new_p), change(ref_p), keep) < 1e-2


def test_learning_rate_matches_program_schedule():
    from repro.train import optim
    lr = optim.cosine_schedule(1e-3, warmup=50, total=1000)
    for s in (0, 1, 2, 49, 50, 51, 500, 999):
        assert abs(float(lr(jnp.asarray(s))) - reference.learning_rate(
            s, 1e-3, 50, 1000)) < 1e-9
