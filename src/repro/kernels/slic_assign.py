"""Pallas TPU kernel for the SLIC assignment step.

The assignment is where SLIC spends its time — every pixel, every
iteration, evaluates a joint color+spatial distance against its 3x3
neighborhood of grid centers. Here each grid step loads one
``(block_rows, Wp)`` row block of every channel plane plus the *entire*
center grid into VMEM (K superpixel centers are a few KB — far smaller
than a pixel tile). For each pixel row of the block it computes the
``(Kp, Wp)`` distances to all K centers — centers on sublanes, pixels on
lanes, channel/spatial terms accumulated in the reference's order —
masks centers outside each pixel's 3x3 grid-cell neighborhood to +inf,
and writes the row's argmin labels.

Masking instead of gathering keeps the kernel gather-free: a pixel's
candidate set is exactly {k : |cell(k) - cell(pixel)| <= 1 per axis},
which is a pure iota/compare predicate on the (Kp, Wp) distance block.
Ties resolve to the lowest center index, matching the reference's
running-min candidate order.

VMEM envelope: a few (Kp, Wp) temporaries per row (Kp is K rounded up
to 128) — 0.4 MB each at K=270, Wp=256 — plus the double-buffered
``(D, block_rows, Wp)`` input block.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
_BIG = 3.4e38


def auto_block_rows(k: int, width: int,
                    budget_bytes: int = 4 * 1024 * 1024) -> int:
    """Pick block_rows so one grid step's distance work, Kp x block_rows
    x Wp floats, stays within ``budget_bytes``: wide images or large
    center grids get shallower row blocks (down to 1), small ones get
    deeper blocks (up to 64, multiples of 8 for sublane alignment)."""
    kp = k + (-k) % LANES
    wp = width + (-width) % LANES
    rows = budget_bytes // (kp * wp * 4)
    if rows >= 8:
        return min(rows - rows % 8, 64)
    return max(int(rows), 1)


def _slic_assign_kernel(x_ref, cen_ref, lab_ref, *, n_channels, k, gy, gx,
                        inv_sy, inv_sx, sw, block_rows):
    i = pl.program_id(0)
    cen = cen_ref[...].astype(jnp.float32)          # (Kp, D+2)
    kp = cen.shape[0]
    wp = x_ref.shape[2]
    # Centers on sublanes, one pixel row on lanes: every operand below
    # is a 2-D (Kp, 1) column or (1, Wp) row broadcast to (Kp, Wp).
    kk = jax.lax.broadcasted_iota(jnp.int32, (kp, 1), 0)
    kgy = kk // gx
    kgx = kk - kgy * gx
    # Pixel x coords and grid-cell columns (reciprocal-multiply, bitwise
    # identical to assign_ref's).
    x = jax.lax.broadcasted_iota(jnp.int32, (1, wp), 1).astype(jnp.float32)
    pcx = jnp.clip((x * inv_sx).astype(jnp.int32), 0, gx - 1)

    def row(r, carry):
        y = jnp.full((1, wp), i * block_rows + r, jnp.int32
                     ).astype(jnp.float32)
        pcy = jnp.clip((y * inv_sy).astype(jnp.int32), 0, gy - 1)
        xs = x_ref[:, pl.ds(r, 1), :].astype(jnp.float32)   # (D, 1, Wp)
        # Joint distances to every center, channel terms first (same
        # accumulation order as assign_ref), then the spatial terms.
        d2 = jnp.zeros((kp, wp), jnp.float32)
        for ch in range(n_channels):
            d2 = d2 + (xs[ch] - cen[:, ch:ch + 1]) ** 2
        d2 = d2 + sw * (y - cen[:, n_channels:n_channels + 1]) ** 2
        d2 = d2 + sw * (x - cen[:, n_channels + 1:n_channels + 2]) ** 2
        # 3x3 grid-cell candidate mask (+ lane padding beyond K).
        valid = ((jnp.abs(kgy - pcy) <= 1) & (jnp.abs(kgx - pcx) <= 1)
                 & (kk < k))
        d2 = jnp.where(valid, d2, _BIG)
        # argmin over centers, ties to the lowest index.
        best = jnp.min(d2, axis=0, keepdims=True)
        lab = jnp.min(jnp.where(d2 == best, kk, kp), axis=0, keepdims=True)
        lab_ref[pl.ds(r, 1), :] = lab
        return carry

    jax.lax.fori_loop(0, block_rows, row, 0)


def slic_assign_pallas(xp: jax.Array, centers: jax.Array, gy: int, gx: int,
                       sy: float, sx: float, sw: float,
                       block_rows: int = 8,
                       interpret: bool = False) -> jax.Array:
    """xp (D, Hp, Wp) padded channel planes, centers (K, D+2) rows
    [features..., y, x] -> labels (Hp, Wp) int32. Hp must divide by
    block_rows and Wp by 128 (``ops.tile_channels`` pads); padded pixels
    get well-formed labels which the caller's validity weights drop."""
    d, hp, wp = xp.shape
    assert hp % block_rows == 0 and wp % LANES == 0, (xp.shape, block_rows)
    k = centers.shape[0]
    assert k == gy * gx and centers.shape[1] == d + 2, (centers.shape, gy, gx)
    kpad = (-k) % LANES
    cpad = jnp.concatenate(
        [centers.astype(jnp.float32),
         jnp.zeros((kpad, d + 2), jnp.float32)])     # masked via kk < k
    kp = k + kpad
    kernel = partial(_slic_assign_kernel, n_channels=d, k=k, gy=gy, gx=gx,
                     inv_sy=float(1.0 / sy), inv_sx=float(1.0 / sx),
                     sw=float(sw), block_rows=block_rows)
    return pl.pallas_call(
        kernel,
        grid=(hp // block_rows,),
        in_specs=[
            pl.BlockSpec((d, block_rows, wp), lambda i: (0, i, 0)),
            pl.BlockSpec((kp, d + 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, wp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((hp, wp), jnp.int32),
        interpret=interpret,
    )(xp, cpad)
