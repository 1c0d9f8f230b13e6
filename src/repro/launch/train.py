"""Distributed LM training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        [--smoke] [--steps N] [--batch B] [--seq S] [--ckpt-dir DIR] \
        [--mesh auto|production|multipod]

On this CPU container use --smoke (reduced config, real optimization); the
full configs are exercised via the dry-run. The same driver runs on a real
TPU slice: the mesh is built from the live device set, the parameters are
placed by the same logical-axis rules the dry-run proved out, and the
training runs through ``train.loop.run_lm_training`` (its step, spans,
prefetch, checkpoints and resume).
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, get_smoke
from repro.data import synthetic
from repro.launch import mesh as mesh_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm, params as params_mod
from repro.sharding import constrain, use_mesh
from repro.train import loop


def place_params(pdefs, params):
    """Lay ``params`` out on the bound mesh as the LM step keeps them: by
    their logical axes, less the mesh axes that do not divide a dimension
    (``sharding.constrain``). The optimizer's moments take this layout
    from the parameters."""
    axes = params_mod.logical_axes(pdefs)
    return jax.jit(lambda p: constrain(p, axes))(params)


def main(argv=None):
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=25)
    p.add_argument("--mesh", default="auto",
                   choices=["auto", "production", "multipod"])
    a = p.parse_args(argv)

    cfg = get_smoke(a.arch) if a.smoke else get_config(a.arch)
    mesh = (mesh_mod.make_mesh_from_devices(
                model_parallel=min(4, len(jax.devices())))
            if a.mesh == "auto" else
            mesh_mod.make_production_mesh(multi_pod=a.mesh == "multipod"))
    pdefs = lm.param_defs(cfg)
    print(f"arch={cfg.name} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"params={params_mod.count(pdefs):,}")

    with use_mesh(mesh):
        params = place_params(
            pdefs, params_mod.materialize(pdefs, jax.random.key(0)))
        res = loop.run_lm_training(
            cfg, params, lm.init_router_state(cfg),
            synthetic.token_batches(a.batch, a.seq, cfg.vocab_size),
            steps=a.steps, lr=a.lr, ckpt_dir=a.ckpt_dir,
            ckpt_every=a.ckpt_every)
    print(f"done at step {res.step}")
    return res


if __name__ == "__main__":
    main()
