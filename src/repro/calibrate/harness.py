"""Calibrate the compute-plane constants against the Pallas kernels.

The analytical model (DESIGN.md §10) carries two fitted dimensionless
constants, both multiplying terms that are exactly zero at the INT8 anchor
(so calibration can NEVER move an int8 result — the anchor invariant):

  * ``mac_mul_share`` — share of the MAC datapath energy in the multiplier
    (vs the accumulate): scales the quadratic-in-bits multiplier term.
    Fitted from the int8 GEMM's measured FLOP mix: one w*a multiply (64
    bit-products at int8) per MAC against the remaining 32-bit adds.
  * ``delivery_width_frac`` — share of the operand-delivery cost that
    scales with the operand-pair width (w+a); the rest is fixed
    control/handshake. Fitted by least squares on measured bytes-per-MAC
    vs (w+a)/16 across the kernel corners.

Measurement: each kernel corner is lowered through ``jax.jit`` in Pallas
interpret mode, on every backend, at a grid-(1,..) shape so XLA's
``cost_analysis()`` FLOP / "bytes accessed" counts are exact (no
while-loop body undercount; see launch/dryrun.py). The fitted constants
are priced numbers, so they must not change with the machine: the counts
come from the interpret-mode program, never from a native TPU compile.
Corners cover three kernels x operand widths:

    int8_matmul     w8  a8    (the INT8 anchor)
    depthwise_conv  bf16/fp32 (same kernel at 16- and 32-bit operands)
    quantize_rows   w32 a8    (the activation-quant streaming pass)

``write_calibrated`` checks the fit + residuals into ``calibrated.json``,
which ``repro.core.devices.load_calibrated`` reads at import; ``check``
re-runs the harness and fails on fit-residual regression (the
``calibrate-smoke`` CI step in benchmarks/run.py).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

CALIB_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "calibrated.json")

# Fit-residual regression gate: a re-run may not exceed the checked-in
# residual by more than this factor (plus an absolute floor for ~zero
# residuals). Same-container re-runs are bit-deterministic; the slack
# covers jax/XLA version drift in cost_analysis bookkeeping.
RESIDUAL_SLACK = 1.25
RESIDUAL_FLOOR = 1e-9


@dataclasses.dataclass
class CalSample:
    """One measured (kernel, precision) corner."""
    kernel: str
    precision: str
    weight_bits: int
    act_bits: int
    macs: int                  # analytic MAC (or element-op) count
    flops: float               # cost_analysis "flops"
    bytes_accessed: float      # cost_analysis "bytes accessed"
    analytic_bytes: float      # operand + result footprint at the widths
    max_abs_err: float         # kernel output vs kernels/ref.py oracle

    @property
    def bytes_per_mac(self) -> float:
        return self.bytes_accessed / self.macs

    @property
    def width_pairs(self) -> float:
        """Operand-pair width in int8-pair units ((w+a)/16; 1.0 at int8)."""
        return (self.weight_bits + self.act_bits) / 16.0


def _cost(lowered) -> Dict[str, float]:
    ca = lowered.compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def run_samples() -> List[CalSample]:
    """Lower, cost-analyze and execute every calibration corner."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.depthwise_conv import depthwise_conv3x3_padded
    from repro.kernels.int8_matmul import int8_matmul
    from repro.kernels.quantize import quantize_rows

    rng = np.random.default_rng(20260808)
    out: List[CalSample] = []

    # --- int8 GEMM, grid (1,1,1): the INT8 anchor corner ------------------
    M = K = N = 128
    a = jnp.asarray(rng.integers(-127, 128, (M, K), dtype=np.int8))
    b = jnp.asarray(rng.integers(-127, 128, (K, N), dtype=np.int8))
    sa = jnp.asarray(rng.random(M, dtype=np.float32))
    sb = jnp.asarray(rng.random(N, dtype=np.float32))
    c = _cost(int8_matmul.lower(a, b, sa, sb, interpret=True))
    got = int8_matmul(a, b, sa, sb, interpret=True)
    err = float(jnp.max(jnp.abs(got - ref.int8_matmul(a, b, sa, sb))))
    out.append(CalSample("int8_matmul", "int8", 8, 8, M * N * K,
                         c["flops"], c["bytes"],
                         M * K + K * N + 4.0 * (M + N) + 4.0 * M * N, err))

    # --- depthwise 3x3, grid (1,1,1), at 16- and 32-bit operands ----------
    B, H, W, C = 1, 8, 16, 128
    x = jnp.asarray(rng.random((B, H, W, C), dtype=np.float32))
    w = jnp.asarray(rng.random((3, 3, C), dtype=np.float32))
    want = ref.depthwise_conv3x3(x, w)
    for prec, dt, bits in (("bf16", jnp.bfloat16, 16), ("fp32", jnp.float32, 32)):
        xd, wd = x.astype(dt), w.astype(dt)
        x_pad = jnp.pad(xd, ((0, 0), (1, 1), (1, 1), (0, 0)))
        c = _cost(depthwise_conv3x3_padded.lower(x_pad, wd, interpret=True))
        got = depthwise_conv3x3_padded(x_pad, wd, interpret=True)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        elems = (B * (H + 2) * (W + 2) * C + 9 * C + B * H * W * C)
        out.append(CalSample("depthwise_conv", prec, bits, bits,
                             B * H * W * C * 9, c["flops"], c["bytes"],
                             elems * bits / 8.0, err))

    # --- quantize (f32 in, int8 codes out), grid (1,) ---------------------
    M, N = 256, 512
    q = jnp.asarray(rng.random((M, N), dtype=np.float32))
    c = _cost(quantize_rows.lower(q, interpret=True))
    codes, scales = quantize_rows(q, interpret=True)
    rc, rs = ref.quantize_rows(q)
    err = max(float(jnp.max(jnp.abs(codes.astype(jnp.int32)
                                    - rc.astype(jnp.int32)))),
              float(jnp.max(jnp.abs(scales - rs))))
    out.append(CalSample("quantize", "w32a8", 32, 8, M * N,
                         c["flops"], c["bytes"],
                         4.0 * M * N + M * N + 4.0 * M, err))
    return out


def fit_constants(samples: Sequence[CalSample]):
    """Fit (constants, residuals) from the measured corners."""
    # delivery: bytes/MAC = k * (w+a)/16 + c over ALL corners (the streaming
    # quantize pass anchors the reuse-free end of the line).
    xs = np.array([s.width_pairs for s in samples])
    ys = np.array([s.bytes_per_mac for s in samples])
    k, c = np.polyfit(xs, ys, 1)
    # degenerate fit (non-positive slope/level) keeps the 0.5 default
    dwf = (float(np.clip(k / (k + c), 0.05, 0.95))
           if k + c > 0 and k > 0 else 0.5)
    pred = k * xs + c
    # scale-free residual: worst corner deviation over the mean level (a
    # per-point denominator would blow up on the GEMM's tiny bytes/MAC)
    fit_rel = float(np.max(np.abs(pred - ys)) / max(np.mean(ys), 1e-12))

    # multiplier share: from the int8 GEMM's measured FLOP mix. One w*a
    # multiply (64 bit-products at int8) per MAC; the remaining measured
    # FLOPs are 32-bit adds (accumulate + epilogue).
    mm = next(s for s in samples if s.kernel == "int8_matmul")
    muls = float(mm.macs)
    adds = max(mm.flops - muls, muls)      # >= one accumulate per MAC
    share = 64.0 * muls / (64.0 * muls + 32.0 * adds)

    dw = next(s for s in samples if s.kernel == "depthwise_conv"
              and s.precision == "fp32")
    residuals = {
        "delivery_fit_rel_err": fit_rel,
        "matmul_flops_rel_dev": abs(mm.flops / (2.0 * mm.macs) - 1.0),
        "dwconv_flops_rel_dev": abs(dw.flops / (2.0 * dw.macs) - 1.0),
        "kernel_max_abs_err": max(s.max_abs_err for s in samples),
    }
    constants = {"mac_mul_share": float(share),
                 "delivery_width_frac": dwf}
    return constants, residuals


def run_calibration() -> Dict:
    import jax
    samples = run_samples()
    constants, residuals = fit_constants(samples)
    return {
        "meta": {"generator": "repro.calibrate.harness",
                 "backend": jax.default_backend(),
                 "jax": jax.__version__,
                 "seed": 20260808},
        "constants": constants,
        "residuals": residuals,
        "samples": [dataclasses.asdict(s) for s in samples],
    }


def write_calibrated(path: str = CALIB_PATH) -> Dict:
    data = run_calibration()
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


def check(path: str = CALIB_PATH, data: Optional[Dict] = None) -> List[str]:
    """Re-run the harness against the checked-in fit; return failures
    (empty list == green). The calibrate-smoke CI gate. Pass ``data`` to
    gate an already-computed ``run_calibration`` result instead of
    re-measuring."""
    with open(path) as f:
        baseline = json.load(f)
    if data is None:
        data = run_calibration()
    fails: List[str] = []
    for name, got in data["residuals"].items():
        ref_val = baseline["residuals"].get(name)
        if ref_val is None:
            fails.append(f"residual {name}: no checked-in baseline")
            continue
        limit = ref_val * RESIDUAL_SLACK + RESIDUAL_FLOOR
        if got > limit:
            fails.append(f"residual {name}: {got:.6g} > limit {limit:.6g} "
                         f"(baseline {ref_val:.6g})")
    for name, got in data["constants"].items():
        ref_val = baseline["constants"].get(name, 0.0)
        if abs(got - ref_val) > 0.05 * max(abs(ref_val), 1e-12):
            fails.append(f"constant {name}: refit {got:.6g} drifted >5% "
                         f"from checked-in {ref_val:.6g}")
    return fails
