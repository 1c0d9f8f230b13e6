"""Public kernel API: the Pallas kernels, compiled for the backend JAX runs.

On TPU the kernels compile natively; on the CPU they execute in
``interpret=True`` mode — the kernel body runs as plain JAX ops with
identical semantics, which is how tests/test_kernels.py validates them
against the ref.py oracles. A shape off a kernel's tiling raises
``ValueError``; no shape is handed to the oracle instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.depthwise_conv import depthwise_conv3x3_padded
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.int8_matmul import int8_matmul as _int8_mm
from repro.kernels.quantize import quantize_rows as _quant
from repro.kernels.ssd_scan import ssd_chunk_scan as _ssd


def interpret_default() -> bool:
    """Interpret mode exactly when JAX's default backend is the CPU."""
    return jax.default_backend() == "cpu"


def int8_matmul(a, b, a_scale, b_scale, *, bm=128, bn=128, bk=128):
    return _int8_mm(a, b, a_scale, b_scale, bm=bm, bn=bn, bk=bk,
                    interpret=interpret_default())


def depthwise_conv3x3(x, w, *, th=8, bc=128):
    """NHWC stride-1 SAME 3x3 depthwise; w: (3,3,C)."""
    x_pad = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return depthwise_conv3x3_padded(x_pad, w, th=th, bc=bc,
                                    interpret=interpret_default())


def flash_attention(q, k, v, *, causal=True, bq=512, bk=512):
    return _flash(q, k, v, causal=causal, bq=bq, bk=bk,
                  interpret=interpret_default())


def ssd_chunk_scan(states, decay):
    return _ssd(states, decay, interpret=interpret_default())


def quantize_rows(x, *, bm=256):
    return _quant(x, bm=bm, interpret=interpret_default())
