"""Fused calibrated quantize: absmax -> scale -> round -> clip, one VMEM pass.

Per-row symmetric INT8 (the activation-quant step of the serving path). Row
tiles live in VMEM once; absmax and the quantized codes are produced without
a second HBM read — on TPU this is a single VPU pass over the tile. The
scales leave the kernel as an (M, 1) column, whose blocks match the TPU's
HBM layout where 1-D (bm,) blocks do not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = s


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def quantize_rows(x: jax.Array, *, bm: int = 256,
                  interpret: bool = False):
    """x: (M, N) float -> (codes int8 (M,N), scales f32 (M,))."""
    M, N = x.shape
    bm = min(bm, M)
    if M % bm:
        raise ValueError(f"{M} rows are not a multiple of the {bm}-row block")
    codes, scales = pl.pallas_call(
        _kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((bm, N), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((M, N), jnp.int8),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    return codes, scales.reshape(M)
