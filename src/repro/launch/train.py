"""Distributed training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        [--smoke] [--steps N] [--batch B] [--seq S] [--ckpt-dir DIR] \
        [--compress-grads] [--mesh auto|production|multipod]

On this CPU container use --smoke (reduced config, real optimization); the
full configs are exercised via the dry-run. The same driver runs on a real
TPU slice: the mesh is built from the live device set and in_shardings come
from the same logical-axis rules the dry-run proved out.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_smoke
from repro.data import synthetic
from repro.launch import mesh as mesh_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.models.params import abstract, logical_axes, materialize
from repro.sharding import fix_divisibility, spec_tree, use_mesh
from repro.train import checkpoint as ckpt_mod
from repro.train import compress as compress_mod
from repro.train import optim


def make_train_step(cfg, lr_fn, out_shardings, compress_grads: bool = False):
    """Jitted (params, opt_state, err, batch, step) -> (..., loss); call it
    inside ``use_mesh`` so the model's sharding annotations bind.
    ``out_shardings`` (from ``shard_train_state``) pins each step's output
    to the layout it was given, so the next step reuses the compiled
    program instead of compiling one for XLA's own choice of layout."""
    def train_step(params, opt_state, err, batch, step):
        (loss, metrics), grads = jax.value_and_grad(
            lm.lm_loss, has_aux=True, argnums=1)(cfg, params, batch)
        if compress_grads:
            q, s, err = compress_mod.compress(grads, err)
            grads = compress_mod.decompress(q, s)
        grads, gnorm = optim.clip_by_global_norm(grads, 1.0)
        params, opt_state = optim.adamw_update(
            grads, opt_state, params, lr=lr_fn(step))
        return params, opt_state, err, loss

    return jax.jit(train_step, donate_argnums=(0, 1, 2),
                   out_shardings=out_shardings)


def shard_train_state(pdefs, params, mesh, compress_grads: bool = False):
    """Place ``params`` by the logical-axis rules and build the AdamW moments
    (and the compression error) with the same shardings, so no device holds
    a full f32 copy. Returns (params, opt_state, err, out_shardings), the
    last for ``make_train_step``."""
    shardings = fix_divisibility(
        spec_tree(logical_axes(pdefs), mesh), abstract(pdefs))
    scalar = NamedSharding(mesh, P())
    opt_sh = optim.AdamWState(shardings, shardings, scalar)
    err_sh = shardings if compress_grads else scalar
    params = jax.device_put(params, shardings)
    opt_state = jax.jit(optim.adamw_init, out_shardings=opt_sh)(params)
    err = (jax.jit(compress_mod.init_error, out_shardings=err_sh)(params)
           if compress_grads else jax.device_put(jnp.zeros(()), scalar))
    return params, opt_state, err, (shardings, opt_sh, err_sh, scalar)


def main():
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
    p.add_argument("--compress-grads", action="store_true")
    p.add_argument("--mesh", default="auto",
                   choices=["auto", "production", "multipod"])
    a = p.parse_args()

    cfg = get_smoke(a.arch) if a.smoke else get_config(a.arch)
    mesh = (mesh_mod.make_mesh_from_devices(
                model_parallel=min(4, len(jax.devices())))
            if a.mesh == "auto" else
            mesh_mod.make_production_mesh(multi_pod=a.mesh == "multipod"))
    print(f"arch={cfg.name} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"params={cfg.param_count():,}")

    pdefs = lm.param_defs(cfg)
    lr_fn = optim.cosine_schedule(a.lr, warmup=max(1, a.steps // 10),
                                  total=a.steps)

    with use_mesh(mesh):
        params, opt_state, err, out_sh = shard_train_state(
            pdefs, materialize(pdefs, jax.random.key(0)), mesh,
            a.compress_grads)
        step_fn = make_train_step(cfg, lr_fn, out_sh, a.compress_grads)

        start = 0
        if a.ckpt_dir and ckpt_mod.latest_step(a.ckpt_dir) is not None:
            tree = {"p": params, "o": opt_state}
            tree, start, _ = ckpt_mod.restore(a.ckpt_dir, tree)
            params, opt_state = tree["p"], tree["o"]
            print(f"resumed from step {start}")

        batches = synthetic.token_batches(a.batch, a.seq, cfg.vocab_size,
                                          start_idx=start * a.batch)
        for step in range(start, a.steps):
            t0 = time.monotonic()
            batch, loader_idx = next(batches)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, err, loss = step_fn(
                params, opt_state, err, batch, jnp.asarray(step))
            if step % 10 == 0 or step == a.steps - 1:
                print(f"step {step:4d} loss {float(loss):.4f} "
                      f"({time.monotonic()-t0:.2f}s/step)")
            if a.ckpt_dir and (step + 1) % a.ckpt_every == 0:
                ckpt_mod.save(a.ckpt_dir, step + 1,
                              {"p": params, "o": opt_state},
                              extra={"loader_idx": loader_idx})
    print("done")


if __name__ == "__main__":
    main()
