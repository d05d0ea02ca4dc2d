"""Plain ``jax.numpy`` FCM and FCM_S: the benchmark's reference.

It imports nothing of the program. It follows the published equations
(Bezdek's FCM, Eqs. 3-4 of arXiv:1601.00072; FCM_S of Ahmed et al.,
IEEE TMI 2002, Eqs. 3'-4') and the convergence rule the configuration
states:

- init: c centers evenly inside the image's intensity range,
  ``lo + (k + 0.5) / c * (hi - lo)``;
- stop: after the first step that moves no center by ``tol`` or more,
  ``tol = eps * (hi - lo) * 0.1``, or after ``max_iters`` steps;
- a pixel at distance 0 from a center belongs to it alone (split evenly
  among equal zero-distance centers);
- labels are the cluster of least (effective) distance, the lowest
  index on a tie.

FCM_S with ``alpha`` and an 8- or 4-neighbourhood: the effective
distance adds ``alpha`` times the mean squared distance of the
in-image neighbours, and the center update runs on
``(x + alpha * xbar) / (1 + alpha)``, ``xbar`` the mean of the
in-image neighbours.

Everything runs in ``dtype`` (float32 for the reference, bfloat16 for
the control), vmapped over a block of images, with float32 matmul
precision at ``highest`` (there is no matmul; the setting guards
against one being added).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

D2_FLOOR = 1e-12
N4 = ((-1, 0), (1, 0), (0, -1), (0, 1))
N8 = N4 + ((-1, -1), (-1, 1), (1, -1), (1, 1))


def _shift(a, dy, dx):
    """out[y, x] = a[y - dy, x - dx], zero outside."""
    h, w = a.shape
    p = jnp.pad(a, ((max(dy, 0), max(-dy, 0)), (max(dx, 0), max(-dx, 0))))
    return p[max(-dy, 0):max(-dy, 0) + h, max(-dx, 0):max(-dx, 0) + w]


def _neighbour_fields(img, v, offsets):
    """(effective-distance term, xbar) of FCM_S for one image."""
    ones = jnp.ones_like(img)
    cnt = jnp.zeros_like(img)
    sx = jnp.zeros_like(img)
    nb = jnp.zeros((v.shape[0],) + img.shape, img.dtype)
    for dy, dx in offsets:
        xs, ws = _shift(img, dy, dx), _shift(ones, dy, dx)
        cnt = cnt + ws
        sx = sx + ws * xs
        nb = nb + ws[None] * (v[:, None, None] - xs[None]) ** 2
    cnt = jnp.maximum(cnt, 1)
    return nb / cnt[None], sx / cnt


def _memberships(d2, m):
    p = jnp.maximum(d2, D2_FLOOR) ** (-1.0 / (m - 1.0))
    u = p / jnp.sum(p, axis=0, keepdims=True)
    zero = d2 <= 0
    nz = jnp.sum(zero, axis=0, keepdims=True)
    u0 = zero.astype(d2.dtype) / jnp.maximum(nz, 1).astype(d2.dtype)
    return jnp.where(nz > 0, u0, u)


def _distances(img, v, alpha, offsets):
    """Effective squared distances (c, H, W) and the update's pixels."""
    d2 = (v[:, None, None] - img[None]) ** 2
    if not offsets:
        return d2, img
    nb, xbar = _neighbour_fields(img, v, offsets)
    return d2 + alpha * nb, (img + alpha * xbar) / (1 + alpha)


def _solve_one(img, c, m, eps, max_iters, alpha, offsets):
    lo, hi = jnp.min(img), jnp.max(img)
    rng = hi - lo
    tol = eps * jnp.where(rng > 0, rng, jnp.ones_like(rng)) * 0.1
    frac = (jnp.arange(c, dtype=img.dtype) + 0.5) / c
    v0 = lo + frac * rng

    def step(v):
        d2, xe = _distances(img, v, alpha, offsets)
        um = _memberships(d2.reshape(c, -1), m) ** m
        den = jnp.maximum(jnp.sum(um, axis=1), D2_FLOOR)
        return jnp.sum(um * xe.reshape(1, -1), axis=1) / den

    def cond(s):
        _, delta, it = s
        return (delta >= tol) & (it < max_iters)

    def body(s):
        v, _, it = s
        vn = step(v)
        return vn, jnp.max(jnp.abs(vn - v)), it + 1

    v, _, it = jax.lax.while_loop(
        cond, body, (v0, jnp.asarray(jnp.inf, img.dtype), jnp.int32(0)))
    return v, it, tol


@partial(jax.jit, static_argnames=("c", "m", "eps", "max_iters", "alpha",
                                   "neighbors", "dtype"))
def _solve_block(imgs, *, c, m, eps, max_iters, alpha, neighbors, dtype):
    offsets = {0: (), 4: N4, 8: N8}[neighbors]
    x = imgs.astype(dtype)
    return jax.vmap(lambda im: _solve_one(im, c, m, eps, max_iters,
                                          alpha, offsets))(x)


@partial(jax.jit, static_argnames=("alpha", "neighbors", "dtype"))
def _labels_block(imgs, v, *, alpha, neighbors, dtype):
    """Labels, and the gap between the two least effective distances
    relative to the second (how clear each pixel's decision is)."""
    offsets = {0: (), 4: N4, 8: N8}[neighbors]

    def one(im, vv):
        d2, _ = _distances(im.astype(dtype), vv.astype(dtype), alpha,
                           offsets)
        lab = jnp.argmin(d2, axis=0).astype(jnp.int32)
        two = jnp.sort(d2.astype(jnp.float32), axis=0)[:2]
        gap = (two[1] - two[0]) / jnp.maximum(two[1], D2_FLOOR)
        return lab, gap
    return jax.vmap(one)(imgs, v)


def solve(imgs: np.ndarray, sem: dict, dtype=jnp.float32, block: int = 64):
    """Centers (B, c), iterations (B,) and tolerances (B,) of every image
    of ``imgs`` (B, H, W), in blocks of ``block`` lanes."""
    kw = dict(c=sem["n_clusters"], m=float(sem["m"]), eps=float(sem["eps"]),
              max_iters=int(sem["max_iters"]), alpha=float(sem["alpha"]),
              neighbors=int(sem["neighbors"]), dtype=dtype)
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(imgs), block):
            blk = _pad_block(imgs[i:i + block], block)
            v, it, tol = _solve_block(jnp.asarray(blk), **kw)
            n = min(block, len(imgs) - i)
            out.append((np.asarray(v, np.float32)[:n], np.asarray(it)[:n],
                        np.asarray(tol, np.float32)[:n]))
    return tuple(np.concatenate(p) for p in zip(*out))


def labels(imgs: np.ndarray, centers: np.ndarray, sem: dict,
           dtype=jnp.float32, block: int = 64):
    """Labels (B, H, W) of ``imgs`` under ``centers`` (B, c), and each
    pixel's relative decision gap."""
    kw = dict(alpha=float(sem["alpha"]), neighbors=int(sem["neighbors"]),
              dtype=dtype)
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(imgs), block):
            n = min(block, len(imgs) - i)
            lab, gap = _labels_block(
                jnp.asarray(_pad_block(imgs[i:i + block], block)),
                jnp.asarray(_pad_block(centers[i:i + block], block),
                            jnp.float32), **kw)
            out.append((np.asarray(lab)[:n], np.asarray(gap)[:n]))
    return tuple(np.concatenate(p) for p in zip(*out))


def _pad_block(a: np.ndarray, block: int) -> np.ndarray:
    if len(a) == block:
        return a
    return np.concatenate([a, np.repeat(a[:1], block - len(a), axis=0)])
