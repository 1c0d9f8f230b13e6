"""Sharding-rule resolution + a reduced-size dry-run on a tiny host mesh.

The full 512-device dry-run is exercised by ``repro.launch.dryrun``
(results in EXPERIMENTS.md); here we prove the same machinery (logical
rules, divisibility fixes, roofline parsing) on an in-process 4-device mesh.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro import sharding as sh
from repro.core import roofline as rl

pytestmark = pytest.mark.skipif(
    jax.device_count() != 1 and jax.device_count() < 4,
    reason="needs exactly the default single-device CPU or >=4 devices")


def _mesh(shape, names):
    """Auto-axis mesh, as the launchers build (repro.launch.mesh)."""
    return jax.make_mesh(shape, names, (AxisType.Auto,) * len(names))


def test_resolve_spec_dedup():
    mesh = _mesh((1,), ("data",))
    with sh.use_mesh(mesh, {"batch": "data", "kv_seq": "data"}):
        spec = sh.resolve_spec(("batch", "kv_seq", None))
        assert spec == P("data", None, None)   # second use dropped


def test_rules_filter_missing_axes():
    mesh = _mesh((1,), ("data",))
    with sh.use_mesh(mesh):                    # no "pod"/"model" axes
        spec = sh.resolve_spec(("batch", "tensor"))
        assert spec == P("data", None)


def test_fix_divisibility_drops_bad_axis():
    mesh = _mesh((1,), ("model",))
    shd = {"x": NamedSharding(mesh, P("model", None))}
    ab = {"x": jax.ShapeDtypeStruct((3, 4), jnp.float32)}
    # 3 % 1 == 0 -> kept with trivial axis; fake a 16-way check via math
    fixed = sh.fix_divisibility(shd, ab)
    assert fixed["x"].spec[0] in ("model", None)


def test_shard_noop_outside_mesh():
    x = jnp.ones((4, 4))
    assert sh.shard(x, "batch", "embed") is x


# ---------------------------------------------------------------------------
# roofline parsing
# ---------------------------------------------------------------------------

HLO_SAMPLE = """
  %ag = bf16[256,1024]{1,0} all-gather(%p0), replica_groups={}
  %ar = f32[128]{0} all-reduce(%x), to_apply=%add
  %rs = bf16[64,64]{1,0} reduce-scatter(%y), dimensions={0}
  %cp = f32[32,32]{1,0} collective-permute(%z)
  %aa.1 = bf16[16,16]{1,0} all-to-all(%w)
  %ags = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-gather-start(%v)
  %notacoll = f32[999]{0} add(%a, %b)
"""


def test_collective_bytes_parser():
    out = rl.collective_bytes(HLO_SAMPLE)
    assert out["all-gather"] == 256 * 1024 * 2 + 8 * 8 * 2 * 2
    assert out["all-reduce"] == 128 * 4
    assert out["reduce-scatter"] == 64 * 64 * 2
    assert out["collective-permute"] == 32 * 32 * 4
    assert out["all-to-all"] == 16 * 16 * 2


# Optimized HLO dumps disambiguate repeated ops with `.N` suffixes on the
# OPCODE itself; the old `[a-z\-]+` matcher silently dropped all of these.
HLO_SUFFIXED = """
  %aa.1 = bf16[128,64]{1,0} all-to-all.1(%w), dimensions={0}
  %ar.23 = f32[16]{0} all-reduce.23(%x), to_apply=%add
  %ags.2 = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-gather-start.2(%v)
  %agd.2 = bf16[8,8]{1,0} all-gather-done.2(%ags.2)
  %cps.1 = (f32[32]{0}, f32[32]{0}, u32[]) collective-permute-start.1(%z)
  %cpd.1 = f32[32]{0} collective-permute-done.1(%cps.1)
  %fused = f32[999]{0} fusion.3(%a, %b), kind=kLoop
  ROOT %ar.root = f32[16]{0} all-reduce.7(%y), to_apply=%add
"""


def test_collective_bytes_suffixed_opcodes():
    out = rl.collective_bytes(HLO_SUFFIXED)
    assert out["all-to-all"] == 128 * 64 * 2
    # one plain suffixed op + one ROOT-prefixed op (the usual final reduce)
    assert out["all-reduce"] == 2 * (16 * 4)
    # async pairs count once: -start carries the (tuple) shape, -done skipped
    assert out["all-gather"] == 2 * (8 * 8 * 2)
    assert out["collective-permute"] == 2 * (32 * 4) + 4
    assert out["reduce-scatter"] == 0


def test_roofline_terms():
    r = rl.Roofline("a", "s", "m", chips=4, hlo_flops=4 * 197e12,
                    hlo_bytes=4 * 819e9, coll_bytes=0.0, coll_by_kind={},
                    model_flops=2 * 197e12)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 1.0) < 1e-9
    assert r.bottleneck in ("compute", "memory")
    assert abs(r.useful_flop_frac - 0.5) < 1e-9
    # step_time = max(1.0, 1.0) = 1s; useful rate = model/(chips*peak) = 0.5
    assert abs(r.roofline_frac - 0.5) < 1e-9


def test_dryrun_machinery_tiny_mesh():
    """lower+compile a smoke train step through the dry-run builder on the
    default (1-device) mesh: proves build_step/in_shardings wiring."""
    import dataclasses
    from repro.configs import get_smoke
    from repro.launch import dryrun, mesh as mesh_mod
    from repro.sharding import fix_divisibility, spec_tree, use_mesh

    cfg = dataclasses.replace(get_smoke("llama3.2-1b"))
    mesh = _mesh((1, 1), ("data", "model"))
    # monkeypatch shapes tiny
    import repro.configs as C
    old = C.SHAPES["train_4k"]
    C.SHAPES["train_4k"] = (32, 2, "train")
    try:
        step_fn, args, axes, donate = dryrun.build_step(cfg, "train_4k")
        shardings = fix_divisibility(spec_tree(axes, mesh, None), args)
        with use_mesh(mesh):
            compiled = jax.jit(
                step_fn, in_shardings=tuple(shardings[k] for k in args),
                donate_argnums=donate
            ).lower(*[args[k] for k in args]).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        assert float(ca.get("flops", 0)) > 0
        assert compiled.memory_analysis() is not None
    finally:
        C.SHAPES["train_4k"] = old


def _capture_lm_steps(monkeypatch):
    """The jitted steps ``train.loop`` builds, as the benchmark wraps them."""
    from repro.train import loop
    jitted, make = [], loop.make_lm_step
    monkeypatch.setattr(loop, "make_lm_step",
                        lambda *a, **kw: jitted.append(make(*a, **kw)) or jitted[-1])
    return jitted


def test_trainer_steps_reuse_one_compiled_program(monkeypatch):
    """The LM step keeps the parameters and moments in the layout the
    launcher placed them in, so step 1 does not compile the step again."""
    from repro.configs import get_smoke
    from repro.data import synthetic
    from repro.launch import mesh as mesh_mod, train
    from repro.models import lm
    from repro.models.params import materialize
    from repro.train import loop

    cfg = get_smoke("llama3.2-1b")
    pdefs = lm.param_defs(cfg)
    jitted = _capture_lm_steps(monkeypatch)
    mesh = mesh_mod.make_mesh_from_devices()
    with sh.use_mesh(mesh):
        params = train.place_params(pdefs, materialize(pdefs, jax.random.key(0)))
        res = loop.run_lm_training(
            cfg, params, lm.init_router_state(cfg),
            synthetic.token_batches(2, 32, cfg.vocab_size), steps=3,
            hooks=loop.TrainHooks(log_every=0))
    assert len(jitted) == 1 and jitted[0]._cache_size() == 1
    assert len(res.losses) == 3 and np.isfinite(res.losses).all()
    leaves = jax.tree.leaves
    for p, m, v in zip(leaves(res.params), leaves(res.opt_state.m),
                       leaves(res.opt_state.v), strict=True):
        assert isinstance(p.sharding, NamedSharding)
        assert m.sharding == p.sharding and v.sharding == p.sharding


def test_launcher_trains_through_the_shared_loop_and_resumes(tmp_path,
                                                              monkeypatch):
    """``launch/train.py`` trains through ``train.loop``: its steps record
    the loop's spans, its checkpoint keeps the loader index, and a second
    call resumes after the checkpoint, in the layout the first one saved
    from, so each call compiles its step once."""
    import time
    from repro import spans
    from repro.launch import train
    from repro.train import checkpoint as ckpt

    # the persistent compile cache would stay on in this test process
    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)
    argv = ["--arch", "llama3.2-1b", "--smoke", "--batch", "2", "--seq", "32",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "3"]

    def steps_run(steps):
        lo = time.time_ns()
        train.main(argv + ["--steps", str(steps)])
        return [k for _, _, name, k in spans.RECORDER.events(lo, time.time_ns())
                if name == "train.step"]

    jitted = _capture_lm_steps(monkeypatch)
    assert steps_run(3) == [0, 1, 2]
    assert ckpt.latest_step(str(tmp_path)) == 3
    assert ckpt.restore(str(tmp_path), {})[2] == {"loader_idx": 6}
    assert steps_run(5) == [3, 4]
    assert [f._cache_size() for f in jitted] == [1, 1]


def test_dryrun_train_step_is_the_trainer_step():
    """On one batch, on one device with no mesh, the dry-run's train step
    and ``make_lm_step`` give bitwise-equal loss and parameters."""
    from repro.configs import get_smoke
    from repro.data import synthetic
    from repro.launch import dryrun
    from repro.models import lm
    from repro.models.params import materialize
    from repro.train import loop, optim

    cfg = get_smoke("mixtral-8x7b")
    batch = {k: jnp.asarray(v) for k, v in
             next(synthetic.token_batches(2, 32, cfg.vocab_size))[0].items()}

    def inputs():          # fresh each call: make_lm_step donates them
        params = materialize(lm.param_defs(cfg), jax.random.key(0))
        return (params, lm.init_router_state(cfg), optim.adamw_init(params),
                batch, jnp.asarray(150))

    dry = jax.jit(dryrun.build_step(cfg, "train_4k")[0])(*inputs())
    ours = loop.make_lm_step(cfg, dryrun.LR_SCHEDULE)(*inputs())
    assert set(dry[3]) == set(ours[3])
    np.testing.assert_array_equal(dry[3]["loss"], ours[3]["loss"])
    for a, b in zip(jax.tree.leaves(dry[0]), jax.tree.leaves(ours[0]),
                    strict=True):
        np.testing.assert_array_equal(a, b)
