"""Block-wise online-softmax attention (prefill path).

Grid (B*H, nq, nk) with nk innermost-sequential; VMEM scratch carries the
running max / denominator / accumulator across K blocks. Causal blocks
entirely above the diagonal are skipped via pl.when (no MXU work issued) —
the TPU analogue of flash-attention's triangular schedule, and the kernel
form of the jnp block-triangular schedule in models/layers.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, nk: int, bq: int, bk: int, causal: bool, scale: float):
    i_q, i_k = pl.program_id(1), pl.program_id(2)

    @pl.when(i_k == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (i_k * bk <= i_q * bq + bq - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)                   # (bq, D)
        k = k_ref[0].astype(jnp.float32)                   # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i_q * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = i_k * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(i_k == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...][:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 512, bk: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q,k,v: (B,H,S,D) -> (B,H,S,D); fp32 softmax, dtype-preserving out."""
    B, H, S, D = q.shape
    bq, bk = min(bq, S), min(bk, S)
    if S % bq or S % bk:
        raise ValueError(f"sequence {S} is not a multiple of the "
                         f"({bq}, {bk}) blocks")
    nq, nk = S // bq, S // bk
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    scale = 1.0 / math.sqrt(D)

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, bq=bq, bk=bk, causal=causal,
                          scale=scale),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
