"""Operations and bytes of every conv and dense layer, from the shapes alone.

The table is the benchmark's own: it walks the configuration file through
``reference.shapes`` and never asks the program. A multiply-add counts as two
operations. Bytes count each operand once at 4 bytes (float32 parameters
and activations): what a layer has to read and write at the least.

Training per layer: the forward product, the gradient of the input (left
out for the first layer, whose input is the image) and the gradient of the
weights, each the size of the forward product.
"""
from __future__ import annotations

from typing import Dict, List

import reference

BYTES = 4
MAC_OPS = ("conv", "dwconv", "dense")


def layer_table(cfg: Dict) -> List[Dict]:
    """One row per conv or dense layer, per image: ``macs``, ``fwd_flops``,
    ``train_flops``, and the bytes of input, weights and output."""
    rows = []
    first = True
    for L, src, out in reference.shapes(cfg):
        op = L["op"]
        if op not in MAC_OPS:
            continue
        k = L.get("k", 1)
        hw_out = out[0] * out[1]
        if op == "conv":
            macs, w = hw_out * k * k * src[2] * out[2], k * k * src[2] * out[2]
        elif op == "dwconv":
            macs, w = hw_out * k * k * out[2], k * k * out[2]
        else:
            macs, w = src[0] * src[1] * src[2] * out[2], src[2] * out[2]
        passes = 2 if first else 3
        rows.append(dict(
            name=L["name"], op=op, k=k, stride=L.get("stride", 1),
            in_hwc=tuple(src), out_hwc=tuple(out), macs=macs,
            fwd_flops=2 * macs, train_flops=2 * macs * passes,
            in_bytes=BYTES * src[0] * src[1] * src[2],
            out_bytes=BYTES * out[0] * out[1] * out[2], w_bytes=BYTES * w,
            train_passes=passes))
        first = False
    return rows


def forward_flops(cfg: Dict) -> int:
    """Operations of one image's forward pass."""
    return sum(r["fwd_flops"] for r in layer_table(cfg))


def train_flops(cfg: Dict) -> int:
    """Operations of one image's training step: 6 x MACs, less the first
    layer's input gradient."""
    return sum(r["train_flops"] for r in layer_table(cfg))


def conv_least_seconds(cfg: Dict, batch: int, peak: Dict, train: bool) -> float:
    """The least time the chip could spend in the conv layers of one call of
    ``batch`` images: for each product (forward; in training also the input
    and weight gradients), the larger of its operations over peak FLOP/s and
    its bytes over peak bytes/s, summed."""
    total = 0.0
    for r in layer_table(cfg):
        if r["op"] == "dense":
            continue
        act_in, act_out = batch * r["in_bytes"], batch * r["out_bytes"]
        flops = batch * r["fwd_flops"]
        products = [act_in + r["w_bytes"] + act_out]        # y = conv(x, w)
        if train:
            products.append(act_in + act_out + r["w_bytes"])  # dw from x, dy
            if r["train_passes"] == 3:
                products.append(act_out + r["w_bytes"] + act_in)  # dx
        for nbytes in products:
            total += max(flops / peak["bf16_flops_per_s"],
                         nbytes / peak["hbm_bytes_per_s"])
    return total
