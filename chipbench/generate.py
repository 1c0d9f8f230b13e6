"""The one traffic generator: host batches from a seed.

A traffic file (``traffic/<name>.json``) says how many rows a batch has and
how many distinct batches the pool holds; the configuration says
which data set its frames look like. Each sample is a pure function of
``(seed, index)``, so the same seed gives the same inputs, and every seed
gives the same sizes. The renderers are copies of the program's synthetic
FPHAB and OpenEDS generators (``repro.data.synthetic``), kept here so that
the yardstick does not move with the program.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def fphab_sample(seed: int, idx: int, hw, channels: int = 3) -> Dict[str, np.ndarray]:
    """An egocentric frame with two rendered hands, and the bounding circle
    of each hand's 21 keypoints (centre = keypoint mean, radius = the largest
    distance), as the paper derives its labels from FPHAB."""
    rng = np.random.default_rng((seed, idx))
    h, w = hw
    img = rng.normal(0.1, 0.05, (h, w, channels)).astype(np.float32)
    yy, xx = np.mgrid[0:h, 0:w]
    centers, radii = [], []
    for _ in range(2):
        kp = rng.normal(0, 0.08, (21, 2)) + rng.uniform(0.25, 0.75, (1, 2))
        kp = np.clip(kp, 0.02, 0.98) * [w, h]
        center = kp.mean(axis=0)
        radius = np.max(np.linalg.norm(kp - center, axis=1))
        blob = np.exp(-2.5 * ((xx - center[0]) ** 2 + (yy - center[1]) ** 2)
                      / max(radius, 1.0) ** 2)
        for c in range(channels):
            img[:, :, c] += blob * rng.uniform(0.4, 0.9)
        centers.append(center / [w, h])
        radii.append(radius / max(h, w))
    return dict(image=np.clip(img, 0, 1),
                center=np.asarray(centers, np.float32),
                radius=np.asarray(radii, np.float32),
                label=np.int32(rng.integers(0, 2)))


def openeds_sample(seed: int, idx: int, hw, channels: int = 1) -> Dict[str, np.ndarray]:
    """A near-infrared eye image of nested ellipses and its 4-class mask
    (background, sclera, iris, pupil), shaped like an OpenEDS frame."""
    rng = np.random.default_rng((seed + 1, idx))
    h, w = hw
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    cx, cy = w * rng.uniform(0.35, 0.65), h * rng.uniform(0.35, 0.65)
    ang = rng.uniform(-0.3, 0.3)
    ca, sa = np.cos(ang), np.sin(ang)
    u = (xx - cx) * ca + (yy - cy) * sa
    v = -(xx - cx) * sa + (yy - cy) * ca
    sc_a, sc_b = w * rng.uniform(0.30, 0.42), h * rng.uniform(0.18, 0.3)
    ir = min(sc_a, sc_b) * rng.uniform(0.45, 0.6)
    pu = ir * rng.uniform(0.3, 0.5)
    mask = np.zeros((h, w), np.int32)
    mask[(u / sc_a) ** 2 + (v / sc_b) ** 2 < 1] = 1
    mask[(u ** 2 + v ** 2) / ir ** 2 < 1] = 2
    mask[(u ** 2 + v ** 2) / pu ** 2 < 1] = 3
    img = 0.45 + 0.1 * rng.standard_normal((h, w, channels)).astype(np.float32)
    img[mask == 1] += 0.25
    img[mask == 2] -= 0.15
    img[mask == 3] -= 0.35
    return dict(image=np.clip(img, 0, 1).astype(np.float32), mask=mask)


DATASETS = {"fphab": fphab_sample, "openeds": openeds_sample}


def batch(cfg: Dict, seed: int, start: int, rows: int) -> Dict[str, np.ndarray]:
    """Rows ``start .. start + rows - 1`` of the seed's data set, stacked."""
    sample = DATASETS[cfg["dataset"]]
    s = [sample(seed, start + i, cfg["input_hw"], cfg["in_channels"])
         for i in range(rows)]
    return {k: np.stack([x[k] for x in s]) for k in s[0]}


def train_pool(cfg: Dict, traffic: Dict, seed: int) -> List[Dict[str, np.ndarray]]:
    """``pool_batches`` batches of ``batch`` rows, no row in two of them."""
    b = traffic["batch"]
    return [batch(cfg, seed, i * b, b) for i in range(traffic["pool_batches"])]
