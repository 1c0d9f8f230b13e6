"""Training-infrastructure tests: optimizer, checkpoint/restart,
fault-tolerance paths."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.train import checkpoint as ckpt
from repro.train import optim


def _quad_problem():
    params = {"w": jnp.asarray([2.0, -3.0, 1.0]), "b": jnp.asarray(4.0)}
    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"] ** 2
    return params, loss


def test_adamw_converges_on_quadratic():
    params, loss = _quad_problem()
    state = optim.adamw_init(params)
    for _ in range(300):
        grads = jax.grad(loss)(params)
        params, state = optim.adamw_update(grads, state, params, lr=5e-2,
                                           weight_decay=0.0)
    assert float(loss(params)) < 1e-3


def test_sgd_converges_on_quadratic():
    params, loss = _quad_problem()
    state = optim.sgd_init(params)
    for _ in range(200):
        grads = jax.grad(loss)(params)
        params, state = optim.sgd_update(grads, state, params, lr=2e-2)
    assert float(loss(params)) < 1e-3


@given(st.floats(0.1, 10.0))
@settings(max_examples=20, deadline=None)
def test_clip_by_global_norm_bound(max_norm):
    g = {"a": jnp.full((4,), 3.0), "b": jnp.full((2, 2), -5.0)}
    clipped, n = optim.clip_by_global_norm(g, max_norm)
    assert float(optim.global_norm(clipped)) <= max_norm * (1 + 1e-5)


def test_cosine_schedule_shape():
    f = optim.cosine_schedule(1.0, warmup=10, total=100)
    assert float(f(jnp.asarray(0))) == 0.0
    assert abs(float(f(jnp.asarray(10))) - 1.0) < 0.11
    assert float(f(jnp.asarray(100))) < 0.01


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"p": {"w": jnp.arange(6.0).reshape(2, 3)},
            "opt": optim.adamw_init({"w": jnp.zeros((2, 3))})}
    ckpt.save(str(tmp_path), 7, tree, extra={"loader_idx": 42})
    out, step, extra = ckpt.restore(str(tmp_path), tree)
    assert step == 7 and extra["loader_idx"] == 42
    np.testing.assert_array_equal(out["p"]["w"], tree["p"]["w"])


def test_checkpoint_resume_latest_and_prune(tmp_path):
    tree = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, {"x": jnp.full(3, float(s))}, keep=2)
    assert ckpt.latest_step(str(tmp_path)) == 5
    out, step, _ = ckpt.restore(str(tmp_path), tree)
    assert step == 5 and float(out["x"][0]) == 5.0
    # pruned to `keep`
    assert len([d for d in os.listdir(tmp_path) if d.startswith("step_")]) == 2


def test_checkpoint_atomic_no_partial(tmp_path):
    """A .tmp directory must never be picked up by restore."""
    os.makedirs(tmp_path / "step_0000000009.tmp")
    ckpt.save(str(tmp_path), 3, {"x": jnp.ones(2)})
    assert ckpt.latest_step(str(tmp_path)) == 3


def test_checkpoint_async_matches_sync(tmp_path):
    tree = {"x": jnp.arange(4.0)}
    t = ckpt.save_async(str(tmp_path), 1, tree)
    t.join()
    out, step, _ = ckpt.restore(str(tmp_path), tree)
    np.testing.assert_array_equal(out["x"], tree["x"])


def test_restore_with_resharding_identity(tmp_path):
    """Mesh-independent restore: device_put with explicit (single-device)
    sharding reproduces the same values — the elastic-restart path."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",), (AxisType.Auto,))
    tree = {"w": jnp.arange(8.0).reshape(2, 4)}
    ckpt.save(str(tmp_path), 1, tree)
    sh = {"w": NamedSharding(mesh, P(None, None))}
    out, _, _ = ckpt.restore(str(tmp_path), tree, shardings=sh)
    np.testing.assert_array_equal(out["w"], tree["w"])


@pytest.mark.parametrize("resharded", [False, True])
def test_restore_bfloat16(tmp_path, resharded):
    """npz keeps bfloat16 as raw 2-byte void; restore reads it back as
    bfloat16, with or without target shardings."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    tree = {"w": jnp.arange(8.0, dtype=jnp.bfloat16).reshape(2, 4)}
    ckpt.save(str(tmp_path), 1, tree)
    mesh = jax.make_mesh((1,), ("data",), (AxisType.Auto,))
    sh = {"w": NamedSharding(mesh, P())} if resharded else None
    out, _, _ = ckpt.restore(str(tmp_path), tree, shardings=sh)
    assert out["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(out["w"], tree["w"])
