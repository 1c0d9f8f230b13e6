"""Compiles for a described TPU v5e chip, with nothing attached: the Pallas
kernels natively (``interpret=False``) at the shapes ``chip_smoke.py`` runs,
and the full-width DetNet train step. What the chip's compiler refuses
(a block off the TPU's tiling, too much VMEM) fails here at no chip time.
Nothing runs, so these say nothing about results or speed."""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.configs import get_config
from repro.models import xr
from repro.models.params import abstract
from repro.train import loop, optim

CASES = {c.name: c for c in chip_smoke.kernel_cases()}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    case = CASES[name]
    args = _on(one_chip, jax.eval_shape(case.make, jax.random.key(0)))
    compiled = jax.jit(partial(case.kernel, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_detnet_train_step_compiles_for_v5e(one_chip):
    cfg = get_config("detnet")
    pdefs, sdefs = xr.param_defs(cfg)
    params = abstract(pdefs)
    opt = jax.eval_shape(optim.adamw_init, params)
    b, (h, w) = 8, cfg.input_hw
    f32 = jnp.float32
    batch = {"image": jax.ShapeDtypeStruct((b, h, w, cfg.in_channels), f32),
             "center": jax.ShapeDtypeStruct((b, 2, 2), f32),
             "radius": jax.ShapeDtypeStruct((b, 2), f32),
             "label": jax.ShapeDtypeStruct((b,), jnp.int32)}
    step = loop.make_xr_step(cfg, xr.circle_loss,
                             optim.cosine_schedule(1e-3, 1, 5))
    args = _on(one_chip, (params, abstract(sdefs), opt, batch,
                          jax.ShapeDtypeStruct((), jnp.int32)))
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30      # one v5e chip's HBM
