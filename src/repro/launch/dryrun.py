"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, WITHOUT allocating a single parameter.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k [--multi-pod] [--out results.jsonl]
    PYTHONPATH=src python -m repro.launch.dryrun --all

For each cell we report ``memory_analysis()`` (fits-per-device proof),
``cost_analysis()`` FLOPs/bytes, and the collective-byte sums parsed from
the HLO — the inputs to EXPERIMENTS.md §Dry-run and §Roofline.
"""
import argparse
import dataclasses
import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs import LM_ARCHS, SHAPES, cell_is_runnable, get_config
from repro.core import roofline as rl
from repro.launch import mesh as mesh_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.models.params import abstract, logical_axes
from repro.sharding import fix_divisibility, spec_tree, use_mesh
from repro.train import loop, optim

LR_SCHEDULE = optim.cosine_schedule(3e-4, warmup=100, total=10_000)


def force_host_devices():
    """Give the CPU backend 512 virtual devices, enough for the multi-pod
    mesh. XLA reads the flag when the backend starts, so call this before
    anything asks JAX for its devices."""
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512"
                               ).strip()
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")   # silence SPMD warnings


def build_step(cfg, shape_name: str):
    """(step_fn, abstract inputs dict, logical-axes dict, donate) for the
    cell. The train step is ``train.loop.lm_step``, whose new parameters and
    moments keep their logical layout, so GSPMD lowers the fsdp gradient
    reductions as reduce-scatter instead of all-reduce (§Perf cell B,
    iteration B3)."""
    _, _, kind = SHAPES[shape_name]
    pdefs = lm.param_defs(cfg)
    params_abs, params_ax = abstract(pdefs), logical_axes(pdefs)

    if kind == "train":
        state_abs = jax.eval_shape(partial(lm.init_router_state, cfg))
        opt_abs = jax.eval_shape(optim.adamw_init, params_abs)
        args = dict(params=params_abs, state=state_abs, opt_state=opt_abs,
                    batch=mesh_mod.input_specs(cfg, shape_name),
                    step=jax.ShapeDtypeStruct((), jnp.int32))
        axes = dict(params=params_ax,
                    state=jax.tree.map(lambda a: (None,) * a.ndim, state_abs),
                    opt_state=optim.AdamWState(params_ax, params_ax, ()),
                    batch=mesh_mod.input_axes(cfg, shape_name), step=())
        return loop.lm_step(cfg, LR_SCHEDULE), args, axes, (0, 1, 2)

    if kind == "prefill":
        def prefill_step(params, batch):
            logits, _ = lm.forward(cfg, params, batch["tokens"],
                                   image_embeds=batch.get("image_embeds"),
                                   encoder_frames=batch.get("encoder_frames"))
            return logits

        batch_abs = mesh_mod.input_specs(cfg, shape_name)
        batch_ax = mesh_mod.input_axes(cfg, shape_name)
        return (prefill_step, dict(params=params_abs, batch=batch_abs),
                dict(params=params_ax, batch=batch_ax), ())

    # decode
    def serve_step(params, cache, batch):
        logits, cache = lm.decode_step(cfg, params, cache,
                                       batch["tokens"], batch["position"])
        return logits, cache

    cache_abs, cache_ax = mesh_mod.decode_state_specs(cfg, shape_name)
    batch_abs = mesh_mod.input_specs(cfg, shape_name)
    batch_ax = mesh_mod.input_axes(cfg, shape_name)
    return (serve_step, dict(params=params_abs, cache=cache_abs,
                             batch=batch_abs),
            dict(params=params_ax, cache=cache_ax, batch=batch_ax), (1,))


def _scaled_cfg(cfg, repeats: int, enc_layers=None):
    """Same block pattern, ``repeats`` copies of the period block, UNROLLED
    so every layer's ops (and collectives) appear in the HLO for costing."""
    import repro.models.lm as _lm
    period = _lm.block_period(cfg)
    kw = dict(num_layers=period * repeats, scan_layers=False)
    if cfg.encoder_layers:
        kw["encoder_layers"] = (enc_layers if enc_layers is not None
                                else cfg.encoder_layers)
    return dataclasses.replace(cfg, **kw)


def _compile_cell(cfg, shape_name, mesh, rules):
    step_fn, args, axes, donate = build_step(cfg, shape_name)
    shardings = fix_divisibility(spec_tree(axes, mesh, rules), args)
    with use_mesh(mesh, rules):
        jitted = jax.jit(step_fn,
                         in_shardings=tuple(shardings[k] for k in args),
                         donate_argnums=donate)
        lowered = jitted.lower(*[args[k] for k in args])
        compiled = lowered.compile()
    return compiled


def _costs(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    coll = rl.collective_bytes(compiled.as_text())
    return (float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0)),
            float(sum(coll.values())), coll)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True):
    """Compile the FULL config (fits-proof + deliverable) and extrapolate
    exact per-step costs from R=1 / R=2 period-block compiles.

    XLA's cost_analysis counts while-loop bodies ONCE (verified empirically),
    so a scan-over-layers program under-reports FLOPs by the trip count.
    Layer stacks are homogeneous in the period block, making per-step cost
    exactly linear in the repeat count R: cost(R) = a + R*b. Two cheap
    compiles recover (a, b); the full R is then priced exactly.
    """
    ok, why = cell_is_runnable(arch, shape_name)
    if not ok:
        return dict(arch=arch, shape=shape_name, skipped=why)
    cfg = get_config(arch)
    import repro.models.lm as _lm
    R_full = _lm.num_repeats(cfg)
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(map(str, mesh.devices.shape))
    rules = mesh_mod.shape_rules(cfg, shape_name)

    t0 = time.monotonic()
    compiled = _compile_cell(cfg, shape_name, mesh, rules)   # full config
    t_compile = time.monotonic() - t0

    # cost extrapolation over the scan trip count
    c1 = _costs(_compile_cell(_scaled_cfg(cfg, 1, enc_layers=1),
                              shape_name, mesh, rules))
    c2 = _costs(_compile_cell(_scaled_cfg(cfg, 2, enc_layers=1),
                              shape_name, mesh, rules))
    slope = [c2[i] - c1[i] for i in range(3)]
    cost = [c1[i] + slope[i] * (R_full - 1) for i in range(3)]
    if cfg.encoder_layers > 1:                # whisper: encoder scan term
        c1e = _costs(_compile_cell(_scaled_cfg(cfg, 1, enc_layers=2),
                                   shape_name, mesh, rules))
        for i in range(3):
            cost[i] += (c1e[i] - c1[i]) * (cfg.encoder_layers - 1)
    flops, byts, coll = cost

    mem = compiled.memory_analysis()
    r = rl.Roofline(arch, shape_name, mesh_name, mesh.devices.size,
                    flops * mesh.devices.size, byts * mesh.devices.size,
                    coll * mesh.devices.size, c2[3],
                    mesh_mod.model_flops(cfg, shape_name))
    row = r.row()
    row.update(
        output_bytes_per_device=getattr(mem, "output_size_in_bytes", 0)
        / mesh.devices.size,
        temp_bytes_per_device=getattr(mem, "temp_size_in_bytes", 0)
        / mesh.devices.size,
        compile_s=round(t_compile, 1), multi_pod=multi_pod)
    if verbose:
        print(f"[{arch} x {shape_name} @ {mesh_name}] "
              f"compile={t_compile:.1f}s "
              f"flops/dev={flops/1e9:.1f}G bytes/dev={byts/1e9:.2f}GB "
              f"coll/dev={coll/1e9:.3f}GB bottleneck={r.bottleneck} "
              f"useful={r.useful_flop_frac:.2f} "
              f"roofline_frac={r.roofline_frac:.3f}", flush=True)
    return row


def main():
    force_host_devices()
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--all", action="store_true")
    p.add_argument("--out", default=None)
    a = p.parse_args()

    archs = LM_ARCHS if (a.all or not a.arch) else [a.arch]
    shapes = list(SHAPES) if (a.all or not a.shape) else [a.shape]
    meshes = [False, True] if a.both_meshes else [a.multi_pod]
    rows = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rows.append(run_cell(arch, shape, mp))
                except Exception as e:
                    rows.append(dict(arch=arch, shape=shape,
                                     multi_pod=mp, error=repr(e)[:500]))
                    print(f"[{arch} x {shape}] FAILED: {e!r}", file=sys.stderr)
                if a.out:
                    with open(a.out, "w") as f:
                        for r in rows:
                            f.write(json.dumps(r) + "\n")
    n_err = sum(1 for r in rows if "error" in r)
    print(f"\n{len(rows)} cells, {n_err} errors")
    sys.exit(1 if n_err else 0)


if __name__ == "__main__":
    main()
