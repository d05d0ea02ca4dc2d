"""SLIC superpixels in JAX (grid-seeded local k-means, gSLICr-style).

SLIC (Achanta et al. 2012; GPU formulation gSLICr, Ren et al. 2015)
over-segments an image into K compact clusters by k-means in the joint
(feature, position) space, with one crucial restriction that makes it
O(N) per iteration instead of O(N·K): centers live on a (gy, gx) grid
and each pixel only ever competes among the ≤ 9 centers of its own and
adjacent grid cells. Both update equations are the weighted sums FCM
already uses, so the whole fit runs device-resident as the same
``centers -> centers'`` fixed point inside
:func:`repro.core.fcm._while_centers`.

Distance (squared, per candidate center k):

    d2 = ||f_i - f_k||^2 + (compactness / S)^2 * ||p_i - p_k||^2

with ``S = sqrt(sy * sx)`` the seed-grid interval, so ``compactness``
trades color fidelity against spatial regularity in the units of the
feature range (10 is the standard choice for 0..255 data).

Two assignment implementations drive the same loop:

* :func:`assign_ref` — pure-jnp: gather the 3x3 candidate centers per
  pixel and keep a running argmin (this module), and
* the Pallas kernel in :mod:`repro.kernels.slic_assign`
  (``use_pallas=True``), which tiles pixels into row blocks with the
  whole (small) center grid resident in VMEM.

Both accumulate the distance terms in the same order and resolve ties
to the lowest center index, so they agree except where a pixel's two
best candidates lie within a few ulp, which two separately compiled
programs may round either way. They are registered in the
:mod:`repro.kernels.ops` dispatch registry under kind ``"slic_assign"``;
``use_pallas=None`` lets the registry pick by platform.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import fcm as F
from repro.core import solver as SV

_BIG = 3.4e38


@dataclasses.dataclass(frozen=True)
class SLICParams:
    """``n_segments`` is the *target* K; the actual K = gy * gx comes
    from :func:`grid_shape` and matches the image aspect. ``tol`` is the
    max center movement (joint feature/pixel units) that counts as
    converged — SLIC needs no fine tolerance, ~10 iterations suffice."""
    n_segments: int = 256
    compactness: float = 10.0
    max_iters: int = 10
    tol: float = 0.25


@dataclasses.dataclass
class SLICResult:
    labels: jax.Array          # (H, W) int32 superpixel ids in [0, K)
    centers: jax.Array         # (K, D+2) rows [features..., y, x]
    counts: jax.Array          # (K,) pixels per superpixel (may be 0)
    gy: int
    gx: int
    n_iters: int
    final_delta: float


def _as_hwd(img: jax.Array) -> jax.Array:
    """Promote (H, W) grayscale to (H, W, 1)."""
    img = jnp.asarray(img, jnp.float32)
    if img.ndim == 2:
        img = img[:, :, None]
    if img.ndim != 3:
        raise ValueError(f"SLIC needs (H, W) or (H, W, D) input, "
                         f"got shape {img.shape}")
    return img


def grid_shape(h: int, w: int, n_segments: int) -> Tuple[int, int]:
    """Seed-grid dims (gy, gx) with roughly square cells and
    gy * gx ~ n_segments."""
    step = max((h * w / max(n_segments, 1)) ** 0.5, 1.0)
    return max(int(round(h / step)), 1), max(int(round(w / step)), 1)


def spatial_weight(h: int, w: int, gy: int, gx: int,
                   compactness: float) -> float:
    """(compactness / S)^2 for the joint distance, S the grid interval."""
    s2 = (h / gy) * (w / gx)
    return float(compactness) ** 2 / s2


def seed_centers(img: jax.Array, gy: int, gx: int) -> jax.Array:
    """Grid seeding: one center per cell at the cell-center pixel,
    features sampled there. Returns (gy*gx, D+2) rows [feat..., y, x]."""
    img = _as_hwd(img)
    h, w, _ = img.shape
    ys = jnp.clip(((jnp.arange(gy) + 0.5) * (h / gy)).astype(jnp.int32),
                  0, h - 1)
    xs = jnp.clip(((jnp.arange(gx) + 0.5) * (w / gx)).astype(jnp.int32),
                  0, w - 1)
    yy, xx = jnp.meshgrid(ys, xs, indexing="ij")
    feats = img[yy, xx]                              # (gy, gx, D)
    pos = jnp.stack([yy.astype(jnp.float32), xx.astype(jnp.float32)],
                    axis=-1)
    return jnp.concatenate([feats, pos], axis=-1).reshape(gy * gx, -1)


def assign_ref(img: jax.Array, centers: jax.Array, gy: int, gx: int,
               sw: float) -> jax.Array:
    """Pure-jnp assignment: each pixel's label is the argmin of the joint
    distance over the ≤ 9 centers of its 3x3 grid-cell neighborhood
    (running min in candidate order == lowest center index on ties, the
    same resolution as the kernel's argmin). Returns (H, W) int32."""
    img = _as_hwd(img)
    h, w, d = img.shape
    grid = centers.reshape(gy, gx, d + 2)
    yy = jax.lax.broadcasted_iota(jnp.float32, (h, w), 0)
    xx = jax.lax.broadcasted_iota(jnp.float32, (h, w), 1)
    # Multiply by the f32 reciprocal (not divide): the Pallas kernel does
    # the same, so cell coords agree bitwise at cell boundaries.
    inv_sy = jnp.float32(1.0 / (h / gy))
    inv_sx = jnp.float32(1.0 / (w / gx))
    pcy = jnp.clip((yy * inv_sy).astype(jnp.int32), 0, gy - 1)
    pcx = jnp.clip((xx * inv_sx).astype(jnp.int32), 0, gx - 1)
    best_d = jnp.full((h, w), _BIG, jnp.float32)
    best_k = jnp.zeros((h, w), jnp.int32)
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            cyc = jnp.clip(pcy + dy, 0, gy - 1)
            cxc = jnp.clip(pcx + dx, 0, gx - 1)
            cand = grid[cyc, cxc]                    # (H, W, D+2)
            d2 = jnp.zeros((h, w), jnp.float32)
            for ch in range(d):                      # same order as kernel
                d2 = d2 + (img[..., ch] - cand[..., ch]) ** 2
            d2 = d2 + sw * (yy - cand[..., d]) ** 2
            d2 = d2 + sw * (xx - cand[..., d + 1]) ** 2
            k = (cyc * gx + cxc).astype(jnp.int32)
            better = d2 < best_d
            best_d = jnp.where(better, d2, best_d)
            best_k = jnp.where(better, k, best_k)
    return best_k


def update_centers(img: jax.Array, labels: jax.Array, old: jax.Array,
                   weights: Optional[jax.Array] = None):
    """Scatter-add center update: each superpixel's new row is the mean
    [feature..., y, x] of its pixels (``weights`` zeroes padded pixels in
    the Pallas path). Empty superpixels keep their old row. Returns
    (centers (K, D+2), counts (K,))."""
    img = _as_hwd(img)
    h, w, d = img.shape
    k = old.shape[0]
    yy = jax.lax.broadcasted_iota(jnp.float32, (h, w), 0)
    xx = jax.lax.broadcasted_iota(jnp.float32, (h, w), 1)
    fp = jnp.concatenate([img, yy[..., None], xx[..., None]],
                         axis=-1).reshape(-1, d + 2)
    wt = (jnp.ones((h * w,), jnp.float32) if weights is None
          else jnp.asarray(weights, jnp.float32).reshape(-1))
    lab = labels.reshape(-1)
    sums = jnp.zeros((k, d + 2), jnp.float32).at[lab].add(wt[:, None] * fp)
    cnt = jnp.zeros((k,), jnp.float32).at[lab].add(wt)
    new = jnp.where(cnt[:, None] > 0, sums / jnp.maximum(cnt, 1.0)[:, None],
                    old)
    return new, cnt


# ---------------------------------------------------------------------------
# Fused fit: assign + update as one center fixed point
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("gy", "gx", "sw", "tol", "max_iters"))
def _slic_loop_ref(img, v0, gy, gx, sw, tol, max_iters):
    from repro.kernels import ops as kops
    assign = kops.build_step("slic_assign", "reference", gy=gy, gx=gx,
                             sw=sw)
    step = lambda v: update_centers(img, assign(img, v), v)[0]
    return SV.while_centers(step, v0, tol, max_iters)


@partial(jax.jit, static_argnames=("h", "w", "gy", "gx", "sw", "tol",
                                   "max_iters", "block_rows", "interpret"))
def _slic_loop_pallas(xpad, wpad, v0, h, w, gy, gx, sw, tol, max_iters,
                      block_rows, interpret):
    from repro.kernels import ops as kops
    assign = kops.build_step("slic_assign", "pallas", h=h, w=w, gy=gy,
                             gx=gx, sw=sw, block_rows=block_rows,
                             interpret=interpret)

    def step(v):
        return update_centers(jnp.moveaxis(xpad, 0, -1), assign(xpad, v),
                              v, weights=wpad)[0]

    return SV.while_centers(step, v0, tol, max_iters)


def fit_slic(img, params: SLICParams = SLICParams(),
             use_pallas: Optional[bool] = False,
             block_rows: Optional[int] = None,
             interpret: Optional[bool] = None) -> SLICResult:
    """Run SLIC to convergence (or ``max_iters``) on a 2-D grayscale or
    (H, W, D) multi-channel image; the assign+update iteration is one
    device-resident ``while_loop`` driven by the solver core's
    convergence test. ``use_pallas=True`` swaps the assignment for the
    tiled Pallas kernel (padding happens once, outside the loop);
    ``use_pallas=None`` lets the :mod:`repro.kernels.ops` registry pick
    by platform; ``block_rows=None`` sizes the kernel's row blocks to
    the VMEM budget for this (K, W)."""
    if use_pallas is None:
        from repro.kernels import ops as kops
        use_pallas = kops.select_step("slic_assign").name == "pallas"
    from repro import obs
    obs.default_registry().counter(
        "slic.fits", impl="pallas" if use_pallas else "reference").inc()
    img = _as_hwd(img)
    h, w, d = img.shape
    gy, gx = grid_shape(h, w, params.n_segments)
    sw = spatial_weight(h, w, gy, gx, params.compactness)
    v0 = seed_centers(img, gy, gx)
    if use_pallas:
        from repro.kernels import ops as kops
        from repro.kernels.slic_assign import auto_block_rows
        if block_rows is None:
            block_rows = auto_block_rows(gy * gx, w)
        xpad, wpad = kops.tile_channels(img, block_rows)
        v, delta, it = _slic_loop_pallas(
            xpad, wpad, v0, h, w, gy, gx, sw, params.tol,
            params.max_iters, block_rows, interpret)
        labels = kops.slic_assign(xpad, v, h, w, gy, gx, sw, block_rows,
                                  interpret)
        _, counts = update_centers(jnp.moveaxis(xpad, 0, -1), labels, v,
                                   weights=wpad)
        labels = labels[:h, :w]
    else:
        v, delta, it = _slic_loop_ref(img, v0, gy, gx, sw, params.tol,
                                      params.max_iters)
        labels = assign_ref(img, v, gy, gx, sw)
        _, counts = update_centers(img, labels, v)
    return SLICResult(labels=labels, centers=v, counts=counts, gy=gy,
                      gx=gx, n_iters=int(it), final_delta=float(delta))
