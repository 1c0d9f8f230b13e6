"""Mamba-2 SSD inter-chunk state scan.

The SSD training form computes per-chunk state contributions in parallel
(batched matmuls, MXU-friendly) and then needs a SEQUENTIAL pass threading
the recurrent state across chunks:  s_{c+1} = s_c * decay_c + states_c.
This kernel runs that pass with the state held in VMEM scratch across
sequential grid steps (grid dim "arbitrary"), emitting the pre-chunk state
s_c each step — one HBM read + one write per chunk, zero re-materialization.
Each chunk's decay enters as a (1, 1) tile of a (B*H, NC, 1, 1) array: the
TPU takes a block whose last two dims equal the array's, where a (1,) slice
of a (NC, 1) column would not be tiled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(states_ref, decay_ref, out_ref, s_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    out_ref[0, 0] = s_ref[...].astype(out_ref.dtype)
    d = decay_ref[0, 0].astype(jnp.float32)             # (1, 1)
    s_ref[...] = (s_ref[...] * d
                  + states_ref[0, 0].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_scan(states: jax.Array, decay: jax.Array, *,
                   interpret: bool = False) -> jax.Array:
    """states: (B, NC, H, P, N); decay: (B, NC, H) -> prev states, same shape
    as ``states`` (state seen by each chunk before its own contribution)."""
    B, NC, H, P, N = states.shape
    sf = states.transpose(0, 2, 1, 3, 4).reshape(B * H, NC, P, N)
    df = decay.transpose(0, 2, 1).reshape(B * H, NC, 1, 1)

    out = pl.pallas_call(
        _kernel,
        grid=(B * H, NC),
        in_specs=[
            pl.BlockSpec((1, 1, P, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, P, N), lambda b, c: (b, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, NC, P, N), states.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(sf, df)
    return out.reshape(B, H, NC, P, N).transpose(0, 2, 1, 3, 4)
