"""INT8 GEMM with fused per-channel dequant — the MXU-native analogue of the
paper's INT8 MAC array (DESIGN.md §3: a systolic-array mapping IS the MXU's
computation; we re-tile for VMEM instead of PE scratchpads).

Tiling: grid (M/bm, N/bn, K/bk), K innermost ("arbitrary" = sequential) so a
VMEM int32 scratch accumulates across K-steps; the dequant epilogue fires on
the last K-step, keeping the int32->f32 conversion out of HBM traffic.
Block shapes default to MXU-aligned (128, 128) tiles. The scales enter as
2-D (M, 1) / (1, N) columns: a 1-D scale block does not match the TPU's
HBM layout of a 1-D array once the grid has more than one block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, as_ref, bs_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * as_ref[...] * bs_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul(a: jax.Array, b: jax.Array, a_scale: jax.Array,
                b_scale: jax.Array, *, bm: int = 128, bn: int = 128,
                bk: int = 128, interpret: bool = False) -> jax.Array:
    """a:(M,K) int8, b:(K,N) int8, a_scale:(M,), b_scale:(N,) -> (M,N) f32."""
    M, K = a.shape
    K2, N = b.shape
    if K != K2:
        raise ValueError(f"inner dims differ: {a.shape} x {b.shape}")
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"(M, N, K)=({M}, {N}, {K}) is not a multiple of "
                         f"the ({bm}, {bn}, {bk}) blocks")
    nk = K // bk

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, a_scale.astype(jnp.float32).reshape(M, 1),
      b_scale.astype(jnp.float32).reshape(1, N))
