"""Seeded BrainWeb-like T1 studies: the benchmark's own copy of the
phantom generator, so the traffic cannot change with the program.

The anatomy is the repository's ellipse phantom (``data/phantom.py``):
four nested classes (0 background, 1 CSF, 2 GM, 3 WM) at T1-like means,
with the anatomy growing and shrinking with the axial slice position.
A study is a stack of axial slices, uint8, with Gaussian noise of a
given sigma (BrainWeb's noise level is a percentage of the brightest
tissue's mean) and optional salt-and-pepper corruption.
"""
from __future__ import annotations

import numpy as np

CLASS_MEANS = np.array([0.0, 52.0, 106.0, 168.0], np.float32)


def _ellipse(yy, xx, cy, cx, ry, rx):
    return ((yy - cy) / ry) ** 2 + ((xx - cx) / rx) ** 2 <= 1.0


def slice_labels(h: int, w: int, pos: float) -> np.ndarray:
    """Ground-truth classes (H, W) of the axial slice at ``pos`` in [0, 1]."""
    yy, xx = np.mgrid[0:h, 0:w]
    cy, cx = h / 2.0, w / 2.0
    s = 0.75 + 0.5 * pos
    lab = np.zeros((h, w), np.int8)
    lab[_ellipse(yy, xx, cy, cx, 0.46 * h * s, 0.42 * w * s)] = 1
    gm = _ellipse(yy, xx, cy, cx, 0.42 * h * s, 0.38 * w * s)
    lab[gm] = 2
    wm = (_ellipse(yy, xx, cy, cx - 0.10 * w, 0.30 * h * s, 0.20 * w * s)
          | _ellipse(yy, xx, cy, cx + 0.10 * w, 0.30 * h * s, 0.20 * w * s))
    lab[wm & gm] = 3
    vent = (_ellipse(yy, xx, cy - 0.02 * h, cx - 0.08 * w, 0.09 * h * s,
                     0.035 * w * s)
            | _ellipse(yy, xx, cy - 0.02 * h, cx + 0.08 * w, 0.09 * h * s,
                       0.035 * w * s))
    lab[vent] = 1
    return lab


def study_labels(h: int, w: int, n_slices: int, pos_lo: float,
                 pos_hi: float) -> np.ndarray:
    """(S, H, W) classes of a study whose slice position drifts from
    ``pos_lo`` to ``pos_hi``."""
    pos = np.linspace(pos_lo, pos_hi, n_slices)
    return np.stack([slice_labels(h, w, float(p)) for p in pos])


def study(labels: np.ndarray, sigma: float, impulse: float,
          rng: np.random.Generator) -> np.ndarray:
    """One uint8 study over ``labels``: class means plus N(0, sigma),
    background at a quarter of the noise (skull-stripped), then a
    fraction ``impulse`` of pixels set to 0 or 255."""
    shape = labels.shape
    img = CLASS_MEANS[labels] + sigma * rng.standard_normal(shape, np.float32)
    bg = 0.25 * sigma * rng.standard_normal(shape, np.float32)
    img = np.where(labels == 0, bg, img)
    out = np.clip(np.rint(img), 0, 255).astype(np.uint8)
    if impulse > 0:
        hit = rng.random(shape, np.float32) < impulse
        salt = rng.random(shape, np.float32) < 0.5
        out[hit & salt] = 255
        out[hit & ~salt] = 0
    return out
