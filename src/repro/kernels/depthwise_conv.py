"""Depthwise 3x3 conv on the VPU (the IRB hot path of the paper's networks).

Depthwise conv has no channel reduction, so the MXU is useless — this is a
VPU kernel with NHWC lane-major tiling: channels ride the 128-lane axis,
image rows tile the sublane axis. The 3x3 window is realized as 9 shifted
multiply-adds — the TPU-idiomatic replacement for Eyeriss-style
row-stationary reuse (VMEM row tiles play the role of PE scratchpads;
DESIGN.md §3).

Halo handling: rather than overlapping block reads (not expressible with
blocked index maps), the pre-padded input is passed as THREE row-shifted
views (XLA slices of one buffer); each grid step then reads aligned
(th, W+2, bc) tiles and writes a clean (th, W, bc) tile.

Any (H, C) runs: the row tile shrinks to a divisor of H, and a channel
count with no 128-multiple divisor (MobileNetV2's 144, 576, 960) takes
all of C as one lane block, which the TPU accepts as a full-extent block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x0_ref, x1_ref, x2_ref, w_ref, o_ref, *, wout: int):
    rows = (x0_ref, x1_ref, x2_ref)
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
    for di in range(3):
        x = rows[di][0].astype(jnp.float32)            # (th, W+2, bc)
        for dj in range(3):
            acc += (x[:, dj:dj + wout, :]
                    * w_ref[di, dj, :].astype(jnp.float32))
    o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("th", "bc", "interpret"))
def depthwise_conv3x3_padded(x_pad: jax.Array, w: jax.Array, *,
                             th: int = 8, bc: int = 128,
                             interpret: bool = False) -> jax.Array:
    """x_pad: (B, H+2, W+2, C) pre-padded by 1px; w: (3,3,C) -> (B,H,W,C)."""
    B, Hp, Wp, C = x_pad.shape
    H, W = Hp - 2, Wp - 2
    th = math.gcd(H, th)
    bc = next((b for b in range(min(bc, C) // 128 * 128, 0, -128)
               if C % b == 0), C)

    x0 = x_pad[:, 0:H]                                  # row r   (top)
    x1 = x_pad[:, 1:H + 1]                              # row r+1 (mid)
    x2 = x_pad[:, 2:H + 2]                              # row r+2 (bottom)

    row_spec = pl.BlockSpec((1, th, Wp, bc), lambda b, i, c: (b, i, 0, c))
    return pl.pallas_call(
        functools.partial(_kernel, wout=W),
        grid=(B, H // th, C // bc),
        in_specs=[row_spec, row_spec, row_spec,
                  pl.BlockSpec((3, 3, bc), lambda b, i, c: (0, 0, c))],
        out_specs=pl.BlockSpec((1, th, W, bc), lambda b, i, c: (b, i, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, C), x_pad.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(x0, x1, x2, w)
