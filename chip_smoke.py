"""Smoke run of the JAX/Pallas plane on one TPU chip, at full width.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the sharded LM trainer only

One process drives every phase and stops, with a non-zero exit, at the
first one that fails:

  1. device   -- JAX's first device must be a TPU; there is no CPU fallback.
  2. detnet   -- 5 training steps of the paper's DetNet (128x128x3) through
                 ``train.loop.run_xr_training``, INT8 PTQ through
                 ``quant.ptq``, and the FP32 forward checked against the
                 same forward on the host CPU.
  3. edsnet   -- the same for EDSNet (384x640x1) with the Dice loss, plus
                 FP32 and INT8 mIoU.
  4. kernels  -- every Pallas kernel compiled natively (``interpret=False``)
                 at a layer shape of a supported config, against its
                 ``kernels/ref.py`` oracle.
  5. pricing  -- the paper's Table 3 sweep through ``Evaluator`` (host numpy).

``--four-chips`` runs only the path ``launch/train.py`` shards: llama3.2-1b
at full width on the mesh ``make_mesh_from_devices`` builds from four
chips, through ``train.loop.run_lm_training`` for 3 steps, with the step-0
loss checked against the same params and batch run forward on one chip.

Weights and data are random, made from ``SEED``. Times printed are from one
smoke run, compilation included where marked; they are not a benchmark.
The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from functools import partial
from typing import Callable, List

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.depthwise_conv import depthwise_conv3x3_padded  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.int8_matmul import int8_matmul  # noqa: E402
from repro.kernels.quantize import quantize_rows  # noqa: E402
from repro.kernels.ssd_scan import ssd_chunk_scan  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import xr  # noqa: E402
from repro.models.params import materialize  # noqa: E402
from repro.quant import ptq  # noqa: E402
from repro.train import loop  # noqa: E402

SEED = 0
TRAIN_STEPS = 5

# FP32 forward, chip vs host CPU. The chip's default precision rounds f32
# conv/matmul operands to bfloat16 (one pass), and its "highest" is not
# bit-exact f32 either. After 5 steps these networks amplify that rounding
# strongly: in eval mode the BatchNorm running statistics do not match the
# activations yet, so no layer renormalizes the error, and in training mode
# near-constant channels divide it by sqrt(var + 1e-5). On a v5e the first
# layer's eval output moved by 2.8e-3 (DetNet) and 3.6e-3 (EDSNet) at the
# default precision and the last by 1.4e-1 and 7.0e-1; the eval outputs at
# "highest" by 6.3e-4 and 2.9e-2; the held-out training loss at the
# default precision by 2.0e-2 and 1.7e-4. The limits below are bounds that
# a wrong result (a wrong layout, a dropped term: an error of order one)
# exceeds, not a claim that the chip is bit-faithful.
TOL_EVAL_HIGHEST = 1e-1
TOL_LOSS_DEFAULT = 1e-1

# llama3.2-1b step-0 loss, four-chip sharded vs one chip: bfloat16 weights
# and activations, and the sharded matmuls sum in another order.
TOL_LM_LOSS = 1e-2


class SmokeFailure(RuntimeError):
    pass


def require(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def rel_l2(got, want) -> float:
    """Relative L2 error over every leaf of two output pytrees."""
    g = np.concatenate([np.asarray(x, np.float64).ravel()
                        for x in jax.tree.leaves(got)])
    w = np.concatenate([np.asarray(x, np.float64).ravel()
                        for x in jax.tree.leaves(want)])
    return float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))


def all_finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**20:.1f} MiB"


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------

def check_device():
    dev = jax.devices()[0]
    require(dev.platform == "tpu",
            f"JAX's first device is {dev.platform!r}, not a TPU")
    print(f"device: {dev.device_kind}, platform {dev.platform}, "
          f"{jax.device_count()} device(s), jax {jax.__version__}")
    return dev


# ---------------------------------------------------------------------------
# phases 2-3: DetNet / EDSNet
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class XRWorkload:
    name: str
    batch: int
    loss_fn: Callable
    batches: Callable          # (cfg, batch, start_idx) -> iterator


XR_WORKLOADS = (
    XRWorkload("detnet", 8, xr.circle_loss,
               lambda cfg, b, i: synthetic.fphab_batches(
                   b, cfg.input_hw, cfg.in_channels, seed=SEED, start_idx=i)),
    XRWorkload("edsnet", 4, xr.dice_loss,
               lambda cfg, b, i: synthetic.openeds_batches(
                   b, cfg.input_hw, seed=SEED, start_idx=i)),
)


def run_xr(wl: XRWorkload, dev):
    cfg = get_config(wl.name)
    pdefs, sdefs = xr.param_defs(cfg)
    params = materialize(pdefs, jax.random.key(SEED))
    state = materialize(sdefs, jax.random.key(SEED + 1))

    step_s: List[float] = []
    res = loop.run_xr_training(
        cfg, params, state, wl.batches(cfg, wl.batch, 0),
        loss_fn=wl.loss_fn, steps=TRAIN_STEPS, ckpt_dir=None, resume=False,
        hooks=loop.TrainHooks(heartbeat=lambda step, dt: step_s.append(dt),
                              log_every=0))
    print(f"{wl.name} {cfg.input_hw}x{cfg.in_channels} batch {wl.batch}: "
          f"losses {res.losses}")
    require(len(res.losses) == TRAIN_STEPS
            and all(math.isfinite(x) for x in res.losses),
            f"{wl.name}: non-finite or missing training loss {res.losses}")
    print(f"{wl.name} smoke step times: first (compile included) "
          f"{step_s[0]:.3f} s, then {[round(t, 4) for t in step_s[1:]]} s; "
          f"peak device memory {peak_bytes(dev)}")
    params, state = res.params, res.extras["state"]

    # FP32 forward: chip (default and highest precision) vs host CPU
    held_out, _ = next(wl.batches(cfg, wl.batch, 10_000))
    batch = {k: jnp.asarray(v) for k, v in held_out.items()}
    forward = jax.jit(lambda p, s, x: xr.forward(cfg, p, s, x)[0])
    train_loss = jax.jit(lambda p, s, b: wl.loss_fn(
        xr.forward(cfg, p, s, b["image"], train=True)[0], b)[0])
    t0 = time.monotonic()
    fp_chip = jax.block_until_ready(forward(params, state, batch["image"]))
    print(f"{wl.name} FP32 forward on the chip, compile included: "
          f"{time.monotonic() - t0:.3f} s (smoke)")
    require(all_finite(fp_chip), f"{wl.name}: non-finite FP32 outputs")
    with jax.default_matmul_precision("highest"):
        fp_chip_hi = forward(params, state, batch["image"])
    loss_chip = float(train_loss(params, state, batch))
    on_cpu = jax.device_put((params, state, batch), jax.devices("cpu")[0])
    fp_cpu = forward(on_cpu[0], on_cpu[1], on_cpu[2]["image"])
    loss_cpu = float(train_loss(*on_cpu))
    err_hi, err_def = rel_l2(fp_chip_hi, fp_cpu), rel_l2(fp_chip, fp_cpu)
    loss_rel = abs(loss_chip - loss_cpu) / abs(loss_cpu)
    print(f"{wl.name} FP32 chip vs CPU: eval outputs relative L2 at highest "
          f"{err_hi:.3e} (limit {TOL_EVAL_HIGHEST:.0e}), at default "
          f"{err_def:.3e} (no limit); held-out training loss at default "
          f"{loss_chip} vs {loss_cpu}, relative {loss_rel:.3e} "
          f"(limit {TOL_LOSS_DEFAULT:.0e})")

    # INT8 PTQ: activation scales from a calibration batch, then inference
    t0 = time.monotonic()
    calib, _ = next(wl.batches(cfg, wl.batch, 20_000))
    acts = jax.jit(lambda p, s, x: xr.forward(cfg, p, s, x,
                                              collect_acts=True)[0]["acts"])
    scales = ptq.calibrate_acts(
        lambda b: acts(params, state, jnp.asarray(b["image"])), [calib])
    require(len(scales) == len(xr.conv_layer_specs(cfg)),
            f"{wl.name}: {len(scales)} activation scales for "
            f"{len(xr.conv_layer_specs(cfg))} MAC layers")
    int8 = jax.jit(lambda p, s, x: ptq.forward_int8(cfg, p, s, x,
                                                    act_scales=scales)[0])
    q = int8(params, state, batch["image"])
    require(all_finite(q) and jax.tree.map(jnp.shape, q)
            == jax.tree.map(jnp.shape, fp_chip),
            f"{wl.name}: INT8 outputs non-finite or mis-shaped")
    print(f"{wl.name} INT8 vs FP32 on the chip, relative L2: "
          f"{rel_l2(q, fp_chip):.3e}; calibration and INT8 inference, "
          f"compile included, {time.monotonic() - t0:.3f} s (smoke)")
    if "mask" in fp_chip:
        miou_fp = float(xr.iou(fp_chip, batch))
        miou_q = float(xr.iou(q, batch))
        require(0.0 <= miou_fp <= 1.0 and 0.0 <= miou_q <= 1.0,
                f"{wl.name}: mIoU out of range")
        print(f"{wl.name} held-out mIoU after {TRAIN_STEPS} steps: "
              f"FP32 {miou_fp:.4f}, INT8 {miou_q:.4f}")


# ---------------------------------------------------------------------------
# phase 4: Pallas kernels at layer shapes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One kernel call: ``kernel(*make(key), interpret=...)`` must match
    ``oracle(*make(key))`` within ``tol``, relative to the oracle's peak."""
    name: str
    kernel: Callable
    oracle: Callable
    make: Callable
    tol: float


def _depthwise(x, w, *, interpret):
    x_pad = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return depthwise_conv3x3_padded(x_pad, w, interpret=interpret)


def _normal(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _int8_mm_args(key, m, k, n):
    ka, kb, ks, kt = jax.random.split(key, 4)
    return (jax.random.randint(ka, (m, k), -127, 128, jnp.int32).astype(jnp.int8),
            jax.random.randint(kb, (k, n), -127, 128, jnp.int32).astype(jnp.int8),
            jax.random.uniform(ks, (m,), minval=1e-3, maxval=1e-2),
            jax.random.uniform(kt, (n,), minval=1e-3, maxval=1e-2))


def _dw_args(key, b, h, w, c):
    kx, kw = jax.random.split(key)
    return _normal(kx, (b, h, w, c)), _normal(kw, (3, 3, c))


def _ssd_args(key, b, nc, h, p, n):
    ks, kd = jax.random.split(key)
    return (_normal(ks, (b, nc, h, p, n)),
            jax.random.uniform(kd, (b, nc, h), minval=0.2, maxval=1.0))


def kernel_cases() -> List[KernelCase]:
    """Each kernel at a layer shape of a supported config: the INT8 GEMM and
    row quantizer at multi-block shapes, the depthwise conv at stride-1
    MobileNetV2 layers of DetNet and EDSNet (144 and 960 channels), flash
    attention at one llama3.2-1b layer over 2048 tokens, the SSD scan at
    one mamba2-1.3b layer over 2048 tokens."""
    cases = [
        KernelCase("int8_matmul_1024", int8_matmul, ref.int8_matmul,
                   partial(_int8_mm_args, m=1024, k=1024, n=1024), 1e-6),
        # a code may differ by one where x/s lands on a rounding tie
        KernelCase("quantize_rows_1024x512", quantize_rows, ref.quantize_rows,
                   lambda key: (_normal(key, (1024, 512)),), 1.0 / 127),
    ]
    for net, layer, batch in (("detnet", "irb2_dw", 8),
                              ("edsnet", "irb2_dw", 4),
                              ("edsnet", "irb14_dw", 4)):
        spec = next(s for s in xr.conv_layer_specs(get_config(net))
                    if s.name == layer)
        h, w = spec.in_hw
        cases.append(KernelCase(
            f"depthwise_{net}_{layer}_{h}x{w}x{spec.in_ch}", _depthwise,
            ref.depthwise_conv3x3,
            partial(_dw_args, b=batch, h=h, w=w, c=spec.in_ch), 1e-5))
    lm = get_config("llama3.2-1b")
    qkv = (1, lm.num_heads, 2048, lm.head_dim)
    # bfloat16 output; the oracle also rounds its probabilities to bfloat16
    cases.append(KernelCase(
        "flash_attention_llama3.2-1b", flash_attention, ref.flash_attention,
        lambda key: tuple(_normal(k, qkv, jnp.bfloat16)
                          for k in jax.random.split(key, 3)), 2e-2))
    ssm = get_config("mamba2-1.3b")
    heads = ssm.ssm_expand * ssm.d_model // ssm.ssm_head_dim
    cases.append(KernelCase(
        "ssd_chunk_scan_mamba2-1.3b", ssd_chunk_scan, ref.ssd_chunk_scan,
        partial(_ssd_args, b=1, nc=2048 // ssm.ssm_chunk, h=heads,
                p=ssm.ssm_head_dim, n=ssm.ssm_state), 1e-5))
    return cases


def run_kernels():
    for i, case in enumerate(kernel_cases()):
        args = case.make(jax.random.key(SEED + i))
        t0 = time.monotonic()
        got = jax.block_until_ready(case.kernel(*args, interpret=False))
        dt = time.monotonic() - t0
        with jax.default_matmul_precision("highest"):
            want = case.oracle(*args)
        err = max(float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                        - w.astype(jnp.float32))))
                  / max(float(jnp.max(jnp.abs(w.astype(jnp.float32)))), 1e-30)
                  for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        shapes = [tuple(a.shape) for a in args]
        print(f"kernel {case.name} {shapes}: error {err:.3e} "
              f"(limit {case.tol:.1e}); first call, compile included, "
              f"{dt:.3f} s (smoke)")
        require(err <= case.tol, f"kernel {case.name} is off its oracle by "
                                 f"{err:.3e}")


# ---------------------------------------------------------------------------
# phase 5: pricing
# ---------------------------------------------------------------------------

def run_pricing():
    from repro.core.experiment import SWEEPS, Evaluator
    t0 = time.monotonic()
    rows = SWEEPS["table3"].rows(Evaluator())
    numbers = [v for r in rows for v in r.values()
               if isinstance(v, (int, float))]
    require(rows and all(math.isfinite(v) for v in numbers),
            "table3: empty or non-finite rows")
    print(f"pricing table3: {len(rows)} rows in {time.monotonic() - t0:.3f} s "
          f"(host numpy); first row {rows[0]}")


# ---------------------------------------------------------------------------
# --four-chips: the sharded LM trainer
# ---------------------------------------------------------------------------

def run_four_chip_lm(batch: int = 8, seq: int = 128, steps: int = 3):
    """``launch/train.py``'s path at its defaults (batch 8, seq 128) and full
    width: parameters placed by ``place_params``, trained through
    ``train.loop.run_lm_training`` inside the mesh."""
    from repro.launch import mesh as mesh_mod
    from repro.launch.train import place_params
    from repro.models import lm, params as params_mod
    from repro.sharding import use_mesh
    from repro.train import loop

    require(jax.device_count() == 4,
            f"--four-chips needs 4 devices, found {jax.device_count()}")
    cfg = get_config("llama3.2-1b")
    pdefs = lm.param_defs(cfg)
    params = materialize(pdefs, jax.random.key(SEED))
    data = lambda: synthetic.token_batches(batch, seq, cfg.vocab_size, seed=SEED)
    first = {k: jnp.asarray(v) for k, v in next(data())[0].items()}

    one_chip = float(jax.jit(partial(lm.loss_and_load, cfg))(params, first)[0])
    mesh = mesh_mod.make_mesh_from_devices(jax.devices())
    print(f"llama3.2-1b {params_mod.count(pdefs):,} params, batch {batch}x{seq}, "
          f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    step_s = []
    with use_mesh(mesh):
        res = loop.run_lm_training(
            cfg, place_params(pdefs, params), lm.init_router_state(cfg),
            data(), steps=steps, lr=3e-4,
            hooks=loop.TrainHooks(heartbeat=lambda step, dt: step_s.append(dt),
                                  log_every=0))
    losses = res.losses
    print(f"llama3.2-1b sharded losses {losses}; one-chip step-0 loss "
          f"{one_chip}")
    print(f"llama3.2-1b smoke step times: first (compile included) "
          f"{step_s[0]:.3f} s, then {[round(t, 4) for t in step_s[1:]]} s; "
          f"peak memory per device "
          f"{[peak_bytes(d) for d in jax.devices()]}")
    require(len(losses) == steps and all(math.isfinite(x) for x in losses),
            f"sharded run ended early or non-finite: {losses}")
    rel = abs(losses[0] - one_chip) / abs(one_chip)
    print(f"step-0 loss, sharded vs one chip: relative difference {rel:.3e} "
          f"(limit {TOL_LM_LOSS:.0e})")
    require(rel <= TOL_LM_LOSS, "sharded step-0 loss is off the one-chip loss")


# ---------------------------------------------------------------------------

def run_phase(name: str, fn: Callable, *args):
    print(f"== phase {name}", flush=True)
    t0 = time.monotonic()
    out = fn(*args)
    print(f"== phase {name}: pass ({time.monotonic() - t0:.1f} s, smoke)",
          flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded llama3.2-1b trainer on 4 chips")
    a = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        # the FP32 reference forward runs on the host CPU device
        jax.config.update("jax_platforms", platforms + ",cpu")
    print(f"compile cache: {enable_compile_cache()}")

    dev = run_phase("device", check_device)
    if a.four_chips:
        run_phase("four-chip llama3.2-1b", run_four_chip_lm)
    else:
        for wl in XR_WORKLOADS:
            run_phase(wl.name, run_xr, wl, dev)
        run_phase("kernels", run_kernels)
        run_phase("pricing", run_pricing)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
