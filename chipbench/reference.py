"""Plain reference for the MobileNetV2 family of XR networks: DetNet and EDSNet.

Written from the published descriptions, in straightforward float32
``jax.numpy``, with every convolution and matrix product at the precision
the configuration states (``matmul_precision``: JAX's default for both
configurations, which on a TPU is one bfloat16 pass with float32 sums). It
imports
nothing of the system under test and takes nothing it has made: the
architecture comes from the configuration file, the weights from the seed.

* MobileNetV2 (arXiv:1801.04381, Table 2): a 3x3 stride-2 stem, then
  inverted-residual blocks (1x1 expand, 3x3 depthwise, 1x1 linear project;
  a residual add where the stride is 1 and the widths match), BatchNorm and
  ReLU6 after every conv but the projection.
* DetNet (arXiv:2206.06780, Fig. 1d): the trunk, a 1x1 conv to 1280
  channels, global average pooling and three regression heads (bounding-circle
  centres of two hands, their radii, a left/right label), each two dense
  layers with a ReLU between them.
* EDSNet (arXiv:2206.06780, Fig. 1e): a UNet on the trunk, as the
  "segmentation models" MobileNetV2-UNet: at each decoder stage a nearest 2x
  upsample, a concat with the encoder feature of that stride (none at
  stride 1), and two 3x3 conv-BN-activation layers; a 3x3 conv to the class
  logits.

Departures from those descriptions, each also made by the system under test,
so that the comparison is of like with like:

* the decoder's activation is ReLU6 (the segmentation-models decoder uses
  ReLU), and its deepest encoder feature is the 320-channel block output (the
  segmentation-models encoder adds the 1280-channel 1x1 conv);
* convolutions pad symmetrically by ``(k - 1) // 2`` at every stride (the
  TensorFlow original pads "SAME", which is asymmetric at stride 2);
* BatchNorm normalises by the biased batch variance, eps 1e-5, and keeps
  running statistics with momentum 0.9; nothing in the published text fixes
  these;
* the losses are the paper's kinds (weighted MSE on circle centre and radius
  plus cross-entropy on the label; soft Dice), with the weights stated in
  ``circle_loss`` and ``dice_loss``;
* the optimiser is AdamW (b1 0.9, b2 0.95, eps 1e-8, weight decay 0.01 on
  every leaf) after clipping the global gradient norm to 1, with a linear
  warm-up and cosine decay of the learning rate.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
BN_EPS = 1e-5
BN_MOMENTUM = 0.9


# ---------------------------------------------------------------------------
# architecture, from the configuration file
# ---------------------------------------------------------------------------

def layers(cfg: Dict) -> List[Dict]:
    """The network as a list of layers, each a dict with ``name`` and ``op``.

    ``op`` is conv | dwconv | dense | gpool | up | cat | add. ``src`` names the
    tensor a layer reads ("" for the previous output), ``skip`` the second
    tensor of a cat or add, and ``save`` the name the output is kept under.
    """
    out: List[Dict] = []

    def conv(name, op, cout, k, stride, act=True, bn=True):
        out.append(dict(name=name, op=op, cout=cout, k=k, stride=stride,
                        act=act, bn=bn, src="", skip="", save=""))

    def save_last(tag):
        out[-1]["save"] = tag

    conv("stem", "conv", cfg["stem_channels"], 3, 2)
    cin, stride_now, taps, block = cfg["stem_channels"], 2, {}, 0
    for t, c, n, s in cfg["stages"]:
        for r in range(n):
            stride = s if r == 0 else 1
            if stride == 2:                 # keep the feature before halving
                taps[stride_now] = f"feat_s{stride_now}"
                save_last(taps[stride_now])
                stride_now *= 2
            residual = stride == 1 and t != 1 and c == cin
            if residual:
                save_last(f"irb{block}_in")
            if t != 1:
                conv(f"irb{block}_expand", "conv", t * cin, 1, 1)
            conv(f"irb{block}_dw", "dwconv", t * cin, 3, stride)
            conv(f"irb{block}_project", "conv", c, 1, 1, act=False)
            if residual:
                out.append(dict(name=f"irb{block}_add", op="add", src="",
                                skip=f"irb{block}_in", save=""))
            cin, block = c, block + 1

    if cfg["task"] == "detection":
        conv("head_conv", "conv", cfg["head_channels"], 1, 1)
        out.append(dict(name="gpool", op="gpool", src="", skip="",
                        save="pooled"))
        for head, dim in cfg["heads"]:
            out.append(dict(name=f"{head}_fc1", op="dense",
                            cout=cfg["head_hidden"], act=True, src="pooled",
                            skip="", save=""))
            out.append(dict(name=f"{head}_out", op="dense", cout=dim,
                            act=False, src="", skip="", save=f"out_{head}"))
    else:
        for i, dc in enumerate(cfg["decoder_channels"]):
            stride_now //= 2
            out.append(dict(name=f"dec{i}_up", op="up", src="", skip="",
                            save=""))
            if stride_now in taps:
                out.append(dict(name=f"dec{i}_cat", op="cat", src="",
                                skip=taps[stride_now], save=""))
            conv(f"dec{i}_conv1", "conv", dc, 3, 1)
            conv(f"dec{i}_conv2", "conv", dc, 3, 1)
        conv("seg_head", "conv", cfg["num_classes"], 3, 1, act=False, bn=False)
        save_last("out_mask")
    return out


def shapes(cfg: Dict) -> List[Tuple[Dict, Tuple[int, int, int], Tuple[int, int, int]]]:
    """``(layer, input (h, w, c), output (h, w, c))`` for every layer."""
    h, w = cfg["input_hw"]
    cur, saved, res = (h, w, cfg["in_channels"]), {}, []
    for L in layers(cfg):
        src = saved[L["src"]] if L["src"] else cur
        op = L["op"]
        if op in ("conv", "dwconv"):
            o = (src[0] // L["stride"], src[1] // L["stride"], L["cout"])
        elif op == "dense":
            o = (1, 1, L["cout"])
        elif op == "gpool":
            o = (1, 1, src[2])
        elif op == "up":
            o = (src[0] * 2, src[1] * 2, src[2])
        elif op == "cat":
            o = (src[0], src[1], src[2] + saved[L["skip"]][2])
        else:
            o = src
        res.append((L, src, o))
        cur = o
        if L["save"]:
            saved[L["save"]] = o
    return res


def param_shapes(cfg: Dict) -> Tuple[Dict, Dict]:
    """``(params, bn_state)`` as nested dicts of shapes, keyed by layer."""
    params, bn = {}, {}
    for L, src, o in shapes(cfg):
        op, cin = L["op"], src[2]
        if op == "conv":
            params[L["name"]] = {"w": (L["k"], L["k"], cin, L["cout"])}
        elif op == "dwconv":
            params[L["name"]] = {"w": (L["k"], L["k"], 1, cin)}
        elif op == "dense":
            params[L["name"]] = {"w": (cin, L["cout"]), "b": (L["cout"],)}
        if op in ("conv", "dwconv") and L["bn"]:
            params[L["name"]]["bn_scale"] = (L["cout"],)
            params[L["name"]]["bn_bias"] = (L["cout"],)
            bn[L["name"]] = {"mean": (L["cout"],), "var": (L["cout"],)}
    return params, bn


def _fan_in(shape: Tuple[int, ...]) -> int:
    """Inputs that feed one output: kh*kw*cin for a conv (cin is 1 per group
    for a depthwise one), cin for a dense layer."""
    return int(np.prod(shape[:-1]))


def init_params(cfg: Dict, key: jax.Array) -> Dict:
    """Weights N(0, 1/fan_in), BatchNorm scale 1 and shift 0, biases 0.

    Call under ``jax.jit`` so the whole tree is made on the device at once."""
    pshapes, _ = param_shapes(cfg)
    names = sorted(pshapes)
    keys = jax.random.split(key, len(names))
    out = {}
    for name, k in zip(names, keys):
        leaf = {}
        for field, shp in pshapes[name].items():
            if field == "w":
                leaf[field] = (jax.random.normal(k, shp, f32)
                               / np.sqrt(_fan_in(shp)))
            elif field == "bn_scale":
                leaf[field] = jnp.ones(shp, f32)
            else:
                leaf[field] = jnp.zeros(shp, f32)
        out[name] = leaf
    return out


def init_bn_state(cfg: Dict) -> Dict:
    _, bshapes = param_shapes(cfg)
    return {n: {"mean": jnp.zeros(s["mean"], f32), "var": jnp.ones(s["var"], f32)}
            for n, s in bshapes.items()}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _conv(x, w, stride: int, groups: int = 1):
    """A conv that pads by ``(k - 1) // 2`` on both sides; ``groups`` equal
    to the channels makes it depthwise."""
    k = w.shape[0]
    p = (k - 1) // 2
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(p, k - 1 - p), (p, k - 1 - p)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=groups)


def forward(cfg: Dict, params: Dict, bn: Dict, images, *, train: bool):
    """Returns ``(outputs, batch_stats)``.

    ``outputs`` maps each head (center, radius, label, or mask) to its
    values; ``batch_stats`` maps each BatchNorm layer to the mean and biased
    variance of its input over the batch (train mode only). The arithmetic
    follows the dtype of ``params`` and ``images``.
    """
    saved, outputs, stats = {}, {}, {}
    x = images
    for L in layers(cfg):
        src = saved[L["src"]] if L["src"] else x
        op, name = L["op"], L["name"]
        if op in ("conv", "dwconv"):
            p = params[name]
            y = _conv(src, p["w"], L["stride"],
                      src.shape[-1] if op == "dwconv" else 1)
            if L["bn"]:
                if train:
                    mean = jnp.mean(y, axis=(0, 1, 2))
                    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
                    stats[name] = {"mean": mean, "var": var}
                else:
                    mean, var = bn[name]["mean"], bn[name]["var"]
                y = ((y - mean) / jnp.sqrt(var + BN_EPS) * p["bn_scale"]
                     + p["bn_bias"])
            if L["act"]:
                y = jnp.minimum(jnp.maximum(y, 0.0), 6.0)
        elif op == "dense":
            p = params[name]
            y = src.reshape(src.shape[0], -1) @ p["w"] + p["b"]
            if L["act"]:
                y = jnp.maximum(y, 0.0)
        elif op == "gpool":
            y = jnp.mean(src, axis=(1, 2), keepdims=True)
        elif op == "up":
            y = jnp.repeat(jnp.repeat(src, 2, axis=1), 2, axis=2)
        elif op == "cat":
            y = jnp.concatenate([src, saved[L["skip"]]], axis=-1)
        else:
            y = src + saved[L["skip"]]
        x = y
        if L["save"]:
            saved[L["save"]] = y
            if L["save"].startswith("out_"):
                outputs[L["save"][4:]] = y
    return outputs, stats


def running_stats(stats: Dict, bn: Dict) -> Dict:
    """BatchNorm running statistics after one update with ``stats``."""
    return {n: {k: BN_MOMENTUM * bn[n][k] + (1 - BN_MOMENTUM) * stats[n][k]
                for k in ("mean", "var")} for n in bn}


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def circle_loss(outputs: Dict, batch: Dict, center_weight: float = 10.0):
    """DetNet: ``center_weight`` x MSE of the two circle centres, plus MSE of
    the radii, plus cross-entropy of the left/right label."""
    center = outputs["center"].reshape(-1, 2, 2)
    mse_c = jnp.mean(jnp.square(center - batch["center"]))
    mse_r = jnp.mean(jnp.square(outputs["radius"] - batch["radius"]))
    logits = outputs["label"]
    logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    ce = -jnp.mean(jnp.take_along_axis(logp, batch["label"][:, None], axis=-1))
    return center_weight * mse_c + mse_r + ce


def dice_loss(outputs: Dict, batch: Dict, eps: float = 1.0):
    """EDSNet: one minus the soft Dice coefficient, per class over the whole
    batch (smoothing ``eps``), averaged over classes."""
    logits = outputs["mask"]
    probs = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(batch["mask"], logits.shape[-1], dtype=probs.dtype)
    inter = jnp.sum(probs * onehot, axis=(0, 1, 2))
    total = jnp.sum(probs, axis=(0, 1, 2)) + jnp.sum(onehot, axis=(0, 1, 2))
    return 1.0 - jnp.mean((2 * inter + eps) / (total + eps))


LOSSES = {"circle": circle_loss, "dice": dice_loss}


# ---------------------------------------------------------------------------
# one training step
# ---------------------------------------------------------------------------

def learning_rate(step: int, base: float, warmup: int, total: int) -> float:
    """Linear warm-up from 0 over ``warmup`` steps, then cosine decay to 0 at
    ``total``."""
    if step < warmup:
        return base * step / max(warmup, 1)
    prog = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return base * 0.5 * (1 + np.cos(np.pi * prog))


def loss_and_grad(cfg: Dict, params: Dict, batch: Dict):
    """Train-mode loss (batch statistics) and its gradient in the dtype of
    ``params``."""
    loss_fn = LOSSES[cfg["loss"]]

    def f(p):
        outs, _ = forward(cfg, p, {}, batch["image"].astype(
            jax.tree.leaves(p)[0].dtype), train=True)
        return loss_fn(outs, batch)

    return jax.value_and_grad(f)(params)


def clip(grads: Dict, max_norm: float = 1.0):
    """Scale the gradient so its global norm is at most ``max_norm``."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(f32)))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g.astype(f32) * scale, grads)


def adamw(grads, m, v, params, count: int, lr, b1=0.9, b2=0.95, eps=1e-8,
          weight_decay=0.01):
    """One AdamW update; moments in float32, parameters keep their dtype.
    ``count`` is the number of updates so far, this one included."""
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def upd(p, a, b):
        pf = p.astype(f32)
        step = (a / bc1) / (jnp.sqrt(b / bc2) + eps) + weight_decay * pf
        return (pf - lr * step).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), m, v


def train_step(cfg: Dict, params: Dict, m: Dict, v: Dict, batch: Dict,
               count: int, lr):
    """Loss, clipped gradient and AdamW update of one step at batch
    statistics. Returns ``(params, m, v, loss, clipped_grads)``."""
    loss, grads = loss_and_grad(cfg, params, batch)
    grads = clip(grads)
    params, m, v = adamw(grads, m, v, params, count, lr)
    return params, m, v, loss, grads
