"""Post-training quantization (paper §2.2, TensorRT-style), any bit width.

Calibrated affine quantization:
  * weights: symmetric per-output-channel scales (minmax),
  * activations: symmetric per-tensor scales from calibration batches
    (minmax or percentile), applied as fake-quant after each conv/dense.

Fake-quant simulates the integer datapath bit-exactly for symmetric scales
(round-to-nearest-even, clip to [-qmax, qmax]) while staying in float — the
standard PTQ evaluation method; the Pallas INT8 kernel (kernels/int8_matmul)
consumes the same scales for true integer execution on TPU.

Every entry point takes ``bits`` (default 8, the paper's INT8). The DSE
plane's precision corners (``experiment.QUANT_CORNERS``) must use the SAME
widths this module emits codes in — ``code_bits`` measures the width a code
tensor actually needs, and tests/test_quant_axis.py ties the two planes.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QMAX = 127.0                       # INT8 default, kept for callers


def qmax(bits: int = 8) -> float:
    """Largest symmetric code at ``bits``: 2^(bits-1) - 1 (127 for INT8)."""
    return float(2 ** (bits - 1) - 1)


def code_bits(codes) -> int:
    """Smallest signed width that holds every code in ``codes`` under the
    symmetric convention (codes in [-(2^(b-1)-1), 2^(b-1)-1])."""
    m = int(np.max(np.abs(np.asarray(codes))))
    b = 2
    while qmax(b) < m:
        b += 1
    return b


def minmax_scale(x: jax.Array, axis=None, bits: int = 8) -> jax.Array:
    """Symmetric scale = absmax / qmax (per-channel if axis given)."""
    if axis is None:
        return jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / qmax(bits)
    red = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
    return jnp.maximum(jnp.max(jnp.abs(x), axis=red), 1e-8) / qmax(bits)


def percentile_scale(x: jax.Array, pct: float = 99.9,
                     bits: int = 8) -> jax.Array:
    return jnp.maximum(jnp.percentile(jnp.abs(x), pct), 1e-8) / qmax(bits)


def quantize_tensor(w: jax.Array, axis: int = -1, bits: int = 8
                    ) -> Tuple[jax.Array, jax.Array]:
    """-> (integer codes, per-channel scale along `axis`). Codes are clipped
    to the symmetric ``bits``-wide range and stored in the narrowest
    standard integer dtype that holds them (sub-byte packing is a
    storage-format concern the DSE plane models via
    ``ConvLayerSpec.weight_bits``)."""
    s = minmax_scale(w, axis=axis, bits=bits)
    shape = [1] * w.ndim
    shape[axis % w.ndim] = -1
    q = jnp.clip(jnp.round(w / s.reshape(shape)), -qmax(bits), qmax(bits))
    dtype = jnp.int8 if bits <= 8 else jnp.int16 if bits <= 16 else jnp.int32
    return q.astype(dtype), s


def fake_quant(x: jax.Array, scale: jax.Array, axis: Optional[int] = None,
               bits: int = 8) -> jax.Array:
    if axis is not None:
        shape = [1] * x.ndim
        shape[axis % x.ndim] = -1
        scale = scale.reshape(shape)
    return jnp.clip(jnp.round(x / scale), -qmax(bits), qmax(bits)) * scale


def _is_weight(path: Tuple, leaf) -> bool:
    key = str(path[-1])
    return ("'w'" in key or "'wq'" in key or "'wk'" in key or "'wv'" in key
            or "'wo'" in key or "'wi" in key or "'we" in key) and (
        hasattr(leaf, "ndim") and leaf.ndim >= 2)


def quantize_params(params, channel_axis: int = -1, bits: int = 8):
    """Fake-quantize every conv/dense weight in a param tree (per-channel)."""
    def f(path, leaf):
        if _is_weight(path, leaf):
            return fake_quant(leaf,
                              minmax_scale(leaf, channel_axis, bits=bits),
                              channel_axis, bits=bits)
        return leaf
    return jax.tree_util.tree_map_with_path(f, params)


def calibrate_acts(forward_fn, batches: Iterable, pct: Optional[float] = 99.9,
                   bits: int = 8) -> Dict[str, float]:
    """Run calibration batches, collect per-layer post-activation scales.

    ``forward_fn(batch) -> Dict[layer_name, activation]`` (the XR model's
    ``forward`` exposes taps via ``collect_acts``). Percentiles are taken on
    the host: a device percentile compiles a sort for every activation
    shape, which takes about 100 s per shape when compiling for a TPU v5e.
    """
    maxes: Dict[str, float] = {}
    for batch in batches:
        acts = forward_fn(batch)
        for name, a in acts.items():
            m = (float(jnp.max(jnp.abs(a))) if pct is None
                 else float(np.percentile(np.abs(np.asarray(a)), pct)))
            maxes[name] = max(maxes.get(name, 0.0), m)
    return {k: max(v, 1e-8) / qmax(bits) for k, v in maxes.items()}


def forward_int8(cfg, params, state, images, act_scales=None, bits: int = 8):
    """XR inference with fake-quantized weights (+ optional act quant);
    ``bits`` reaches BOTH planes: weight fake-quant here, activation
    saturation inside ``xr.forward`` (scales from ``calibrate_acts`` must
    use the same width)."""
    from repro.models import xr
    qparams = quantize_params(params, bits=bits)
    return xr.forward(cfg, qparams, state, images, train=False,
                      act_scales=act_scales, act_bits=bits)


def weight_histogram(params, bins: int = 101, rng=(-0.5, 0.5)
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Paper Fig 1(i): weight-value histogram across all layers."""
    leaves = [np.asarray(l, np.float32).ravel()
              for l in jax.tree.leaves(params)
              if hasattr(l, "ndim") and l.ndim >= 2]
    allw = np.concatenate(leaves)
    return np.histogram(allw, bins=bins, range=rng)
