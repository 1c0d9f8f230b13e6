"""The VLM cell's yardstick on the CPU: ``reference_vlm`` against the
program's own plain reference, its parameter tree against the program's at
full width, ``flops_vlm`` against a hand count, the stage and roofline
readers on a synthetic trace, and ``drive_vlmtrain`` end to end at a tiny
size, where a planted fault has to come out not correct."""
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import drive_vlmtrain
import flops_vlm
import reference_vlm
import run
import vlmtrace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3_000_000_019


def full():
    with open(os.path.join(BENCH, "configs", "kimi_vl_a3b.json")) as fh:
        return json.load(fh)


def tiny(cfg, traffic=None):
    """Every layer kind at CPU widths: 28x28 frames (4 patches, 1 image
    token), 16 experts of which 4 held, 2 a token."""
    cfg = dict(cfg, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               vocab_size=256, n_routed_experts=4, router_width=16,
               num_experts_per_tok=2, num_hidden_layers=3, image_size=28,
               vision_config=dict(cfg["vision_config"], hidden_size=32,
                                  num_attention_heads=4, intermediate_size=48,
                                  num_hidden_layers=2, init_pos_emb_height=3,
                                  init_pos_emb_width=3))
    if traffic is None:
        return cfg
    return cfg, dict(traffic, batch=4, seq_len=32, reference_microbatch=2)


def test_parameter_tree_is_the_program_s_at_full_width():
    from repro.models import lm
    from repro.models.params import abstract
    cfg = full()
    want = jax.tree.map(lambda s: tuple(s.shape),
                        abstract(lm.param_defs(drive_vlmtrain.program_config(cfg))))
    got = jax.tree.map(lambda s: tuple(s[0]), reference_vlm.shapes(cfg),
                       is_leaf=reference_vlm._is_spec)
    assert got == want


@pytest.mark.parametrize("part", ["loss", "grads"])
def test_reference_matches_the_program_s_reference(part):
    """At float32 and the highest precision the two references, written
    apart, agree to round-off on the same weights and batch."""
    from repro.models import ref_kimi_vl
    cfg = dict(tiny(full()), compute_dtype="float32")
    pcfg = drive_vlmtrain.program_config(cfg)
    params = reference_vlm.init_params(cfg, jax.random.key(1))
    batch = {k: jnp.asarray(v) for k, v in drive_vlmtrain.vlm_batch(
        cfg, {"seq_len": 32}, SEED, 0, 2).items()}
    bias = jax.random.normal(jax.random.key(2), (2, 16)) * 0.01
    with jax.default_matmul_precision("highest"):
        ours = jax.value_and_grad(reference_vlm.loss_and_load, argnums=1,
                                  has_aux=True)(cfg, params, batch, bias)
        theirs = jax.value_and_grad(ref_kimi_vl.loss_and_load, argnums=1,
                                    has_aux=True)(pcfg, params, batch, bias)
    if part == "loss":
        np.testing.assert_allclose(float(ours[0][0]), float(theirs[0][0]), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(ours[0][1]), np.asarray(theirs[0][1][0]))
    else:
        for a, b in zip(jax.tree.leaves(ours[1]), jax.tree.leaves(theirs[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                       atol=2e-6 * float(np.abs(np.asarray(b)).max()))


def test_train_flops_against_a_hand_count():
    """Forward multiply-adds of one 1,024-token sequence, written out:
    patches 1,024 x 588 x 1,152; tower 4 x (1,024 x (3,981,312 + 1,327,104
    + 9,916,416) + 2 x 1,024^2 x 1,152); projector 256 x (4,608^2 + 4,608 x
    2,048); MLA 5 x (1,024 x 13,762,560 + 524,800 x 16 x 320); dense
    1,024 x 3 x 2,048 x 11,264; router 4 x 1,024 x 2,048 x 64; shared
    4 x 1,024 x 3 x 2,048 x 2,816; routed 4 x 1,024 x 0.75 x 3 x 2,048 x
    1,408; head 768 x 2,048 x 20,480."""
    macs = (1024 * 588 * 1152
            + 4 * (1024 * (3_981_312 + 1_327_104 + 9_916_416) + 2 * 1024 ** 2 * 1152)
            + 256 * (4608 ** 2 + 4608 * 2048)
            + 5 * (1024 * 13_762_560 + 524_800 * 16 * 320)
            + 1024 * 3 * 2048 * 11264
            + 4 * 1024 * 2048 * 64
            + 4 * 1024 * 3 * 2048 * 2816
            + 4 * 1024 * 0.75 * 3 * 2048 * 1408
            + 768 * 2048 * 20480)
    want = 2 * (3 * macs - 1024 * 588 * 1152)
    assert flops_vlm.train_flops(full(), 1024) == pytest.approx(want, rel=1e-12)
    assert 2.0e12 < want < 2.5e12


def test_routed_gmm_least_time_is_compute_bound_at_the_cell_s_size():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    slots = 12_288 * 4                  # a step's slots on held experts
    t = flops_vlm.routed_gmm_least_seconds(full(), slots, 4, peak)
    assert t == pytest.approx(18 * slots * 2048 * 1408 / 197e12, rel=1e-12)


# ---------------------------------------------------------------------------
# the stage readers on a synthetic trace
# ---------------------------------------------------------------------------

def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _profile(ops, modules):
    lines = [SimpleNamespace(name="XLA Ops", events=[_ev(*o) for o in ops]),
             SimpleNamespace(name="XLA Modules", events=[_ev(*m) for m in modules])]
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/device:TPU:0", lines=lines, stats=[]),
        SimpleNamespace(name="Task Environment", lines=[],
                        stats=[("profile_start_time", 1_000)])])


HLO = """
  %fusion.1 = f32[4] fusion(%p), kind=kLoop, metadata={op_name="jit(step_fn)/while/body/checkpoint/vision/add"}
  %fusion.2 = bf16[4] fusion(%q), metadata={op_name="jit(step_fn)/transpose(jvp(mla))/dot_general"}
  ROOT %fusion.3 = f32[4] fusion(%q), metadata={op_name="jit(step_fn)/checkpoint/rematted_computation/moe.router/logistic"}
  %ragged-dot-none.3 = f32[8] custom-call(%a), metadata={op_name="ragged-dot-none"}
  %ragged-dot-metadata.1 = s32[9] custom-call(%a), metadata={op_name="ragged-dot-metadata"}
  %fusion.9 = f32[4] fusion(%q), metadata={op_name="jit(step_fn)/add"}
  %while.2 = (s32[], f32[4]{0:T(128)}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/jvp(vision)/while"}
"""


def test_stage_of_an_op():
    names = vlmtrace.op_names(HLO)
    assert names["fusion.3"].endswith("moe.router/logistic")
    assert "while.2" not in names          # a loop's op spans its body's
    assert [vlmtrace.stage(n, names.get(n)) for n in (
        "fusion.1", "fusion.2", "fusion.3", "ragged-dot-none.3",
        "ragged-dot-metadata.1", "fusion.9")] == [
        "vision", "mla", "moe.router", "moe.routed", None, None]
    assert vlmtrace.stage("x", "jit(f)/moe.routed_extra/add") is None


def test_stage_seconds_keep_to_the_step_module_and_the_window():
    # window [1,000, 1,100] ns after the profile's start (host clock 2,000-2,100)
    ops = [("%while.2 = (s32[], f32[4]) while(...)", 1_000, 45),  # around fusion.1
           ("%fusion.1 = f32[4] fusion(...)", 1_000, 40),      # vision, 40
           ("%fusion.2 = bf16[4] fusion(...)", 1_050, 30),     # mla, 30
           ("%ragged-dot-none.3 = ...", 1_080, 50),            # clipped to 20
           ("%fusion.1 = f32[4] fusion(...)", 1_200, 10)]      # other module
    modules = [("jit_step_fn(7)", 1_000, 150), ("jit_copy(1)", 1_195, 20)]
    sec = vlmtrace.stage_seconds(_profile(ops, modules), [(2_000, 2_100, "window")],
                                 vlmtrace.op_names(HLO))
    assert sec == pytest.approx({"vision": 40e-9, "mla": 30e-9,
                                 "moe.routed": 20e-9, "gmm": 20e-9})


def test_share_and_roofline_readers(monkeypatch):
    import importlib.util
    sec = {"vision": 1.0, "projector": 0.5, "mla": 2.0, "moe.routed": 3.0,
           "moe.router": 0.25, "gmm": 2.0}
    monkeypatch.setattr(vlmtrace, "seconds", lambda ctx: sec)
    outcome = SimpleNamespace(info={"traced_routed_slots": 10 ** 6, "traced_steps": 2})
    ctx = dict(cfg=full(), trace={"busy_s": 10.0}, outcome=outcome,
               peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})

    def read(name):
        path = os.path.join(BENCH, "metrics", f"{name}.py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)

    assert read("vlm.vision_share") == pytest.approx(15.0)
    assert read("vlm.mla_share") == pytest.approx(20.0)
    assert read("vlm.moe_share") == pytest.approx(32.5)
    least = flops_vlm.routed_gmm_least_seconds(full(), 10 ** 6, 8, ctx["peak"])
    assert read("vlm.routed_gmm_roofline") == pytest.approx(100 * least / 2.0)
    monkeypatch.setattr(vlmtrace, "seconds", lambda ctx: None)
    assert read("vlm.vision_share") is None
    assert read("vlm.routed_gmm_roofline") is None


# ---------------------------------------------------------------------------
# drive_vlmtrain, end to end on the CPU
# ---------------------------------------------------------------------------

def _result(capsys, faults=()):
    rc = run.main(["--workload", "kimi_vl_a3b.train", "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0"], faults=frozenset(faults),
                  require_chip=False, overrides=tiny)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("faults,correct", [((), True)] + [
    ((f,), False) for f in drive_vlmtrain.FAULTS])
def test_fault_is_caught(capsys, faults, correct):
    res = _result(capsys, faults)
    assert res["correct"] is correct, res["checks"]
    assert res["metrics"]["train_images_per_s"]["value"] > 0
