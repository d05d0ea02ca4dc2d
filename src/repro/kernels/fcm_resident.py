"""Pallas TPU kernel: the VMEM-resident whole-solve FCM loop.

Histogram- and superpixel-compressed problems are tiny — at most 256
weighted rows and a handful of centers — so the *entire* fixed point
fits in VMEM. Instead of dispatching one fused-step kernel per
iteration (every iteration pays a launch plus an HBM round-trip for the
centers), this kernel runs the complete convergence loop
(``lax.while_loop`` over the weighted center step with the
``max|v' - v| < tol`` stop test of
:func:`repro.core.solver.while_centers`) inside ONE ``pallas_call``:
zero HBM traffic after the initial row load, zero per-iteration
dispatch. That is the paper's 245x lesson (all stages device-resident,
§5) taken to its limit for the compressed problems the serving engine
actually runs.

Batched form: the grid iterates over lanes, each grid step solving its
lane to ITS OWN convergence point — per-lane trajectories are identical
to solo :func:`repro.core.solver.while_centers` runs, with no frozen-lane
masking work at all.

Rows are tiled ``(D, R, 128)`` per lane with zero-weight padding;
centers travel lane-broadcast as ``(c, D, 1, 128)`` blocks.

Two residency extensions lift the whole-solve shape to real workloads:

* :func:`resident_streamed_solve_pallas` — same convergence loop, but
  the rows live in HBM and are double-buffered into VMEM in
  ``(STREAM_CHUNK_ROWS, 128)`` tiles per center step (async copy into
  one buffer slot while the other is reduced), so only the centers and
  the running Eq. 3 partials stay resident. That lifts the row bound
  from ``MAX_ROWS`` (256) to ``STREAM_MAX_ROWS`` (tens of thousands):
  superpixel/vector problems run their complete fixed point in ONE
  ``pallas_call``.
* :func:`resident_stencil_solve_pallas` — the FCM_S analogue: a whole
  padded pixel grid (plus validity sheet) sits in VMEM and the fused
  stencil + membership + center reduction iterates to convergence
  inside the kernel, collapsing the spatial route's per-iteration
  dispatch entirely. Stencil semantics (zero-filled shifts, per-pixel
  neighbor counts, Eq. 3' on the effective pixels) mirror
  :func:`repro.core.spatial.neighbor_fields` /
  :func:`~repro.core.spatial.spatial_center_step` exactly, with the
  validity sheet standing in for the image border.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fcm_membership import membership_from_d2_tile

LANES = 128
_D2_FLOOR = 1e-12

#: VMEM eligibility bounds (what "the problem fits" means for dispatch).
MAX_ROWS = 256
MAX_C = 8
MAX_FEAT = 8

#: HBM-streamed variant: rows per DMA chunk (R axis), and the row bound
#: for dispatch. VMEM holding the stream is only the double buffer
#: (2 * D * STREAM_CHUNK_ROWS * 128 f32 = 512 KiB at D=8), so the row
#: bound is a wall-clock choice, not a fit constraint: the roofline
#: report (benchmarks/roofline_report.py) measures the streamed cell at
#: probe sizes up to this bound to keep it honest.
STREAM_CHUNK_ROWS = 8
STREAM_MAX_ROWS = 131072

#: Resident stencil bounds: the padded grid, validity sheet, the
#: hoisted neighborhood fields and the (c, *grid) membership
#: temporaries must all sit in VMEM. The bound counts *padded* pixels
#: (:func:`stencil_pixels`): a thin image can pad to many times its
#: own size. At the c=8 / 64k-pixel corner the v5e compiler asks for
#: 16.7 MiB, past the 16 MiB default scoped-VMEM limit, so the kernel
#: raises its limit to ``STENCIL_VMEM_BYTES``.
STENCIL_MAX_PIXELS = 65536
STENCIL_MAX_C = 8
STENCIL_VMEM_BYTES = 32 * 1024 * 1024


def stencil_pixels(grid_shape) -> int:
    """Pixels of one lane's grid after ``ops.tile_grid_batched`` pads H
    to 8 and W to 128 (what the resident stencil solve holds in VMEM
    and what its dispatch bound counts)."""
    *lead, h, w = grid_shape
    n = (h + (-h) % 8) * (w + (-w) % LANES)
    for d in lead:
        n *= d
    return int(n)


# Mosaic lays a block's two minor dims out as (8, 128) tiles, needs them
# aligned or spanning the array, and rejects 1-D vectors that a
# reduction produces and a broadcast reuses. So every in-kernel value
# here keeps rank >= 2: reductions keep their dims, centers travel as
# (c, D, 1, 128) lane-replicated blocks and are carried as (c, D, 1, 1),
# and the per-lane scalars (tol in; delta, iters out) travel as
# (B, 1, 128) arrays in (1, 1, 128) blocks.

def _lane_scalar_spec():
    return pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0))


def _lane_scalar_shape(b: int, dtype):
    return jax.ShapeDtypeStruct((b, 1, LANES), dtype)


def _lane_scalars(a: jax.Array) -> jax.Array:
    """(B,) -> (B, 1, 128) lane-replicated float32."""
    return jnp.broadcast_to(a.astype(jnp.float32)[:, None, None],
                            (a.shape[0], 1, LANES))


def _converge(step, v0, tol_ref, max_iters, v_ref, delta_ref, it_ref):
    """Run the solver core's stop test in-kernel and write the lane's
    centers (broadcast over the 128 lanes), residual and iterations."""
    from repro.core.solver import while_centers
    v, delta, it = while_centers(step, v0, tol_ref[0, 0, 0], max_iters)
    v_ref[...] = jnp.broadcast_to(v[None], v_ref.shape)
    delta_ref[...] = jnp.full(delta_ref.shape, delta, jnp.float32)
    it_ref[...] = jnp.full(it_ref.shape, it, jnp.int32)


def _lane_sum(a: jax.Array) -> jax.Array:
    return jnp.sum(a, axis=-1, keepdims=True)


def _row_partials(x, w, v, m: float):
    """Eq. 3 partials of rows ``x`` (D, R, 128) weighted by ``w``
    (R, 128) at centers ``v`` (c, D, 1, 1): ``num`` (c, D, 1, 128) and
    ``den`` (c, 1, 1, 128), summed over rows but not yet over lanes."""
    d2 = jnp.sum((v - x[None]) ** 2, axis=1)         # (c, R, 128)
    um = (membership_from_d2_tile(d2, m) ** m) * w[None]
    num = jnp.sum(um[:, None] * x[None], axis=2, keepdims=True)
    den = jnp.sum(um[:, None], axis=2, keepdims=True)
    return num, den


def _centers(num, den):
    return _lane_sum(num) / jnp.maximum(_lane_sum(den), _D2_FLOOR)


def _resident_kernel(x_ref, w_ref, v0_ref, tol_ref,
                     v_ref, delta_ref, it_ref, *, m: float, max_iters: int):
    x = x_ref[0].astype(jnp.float32)                  # (D, R, 128)
    w = w_ref[0].astype(jnp.float32)                  # (R, 128)
    v0 = v0_ref[0][..., :1].astype(jnp.float32)       # (c, D, 1, 1)
    _converge(lambda v: _centers(*_row_partials(x, w, v, m)), v0, tol_ref,
              max_iters, v_ref, delta_ref, it_ref)


def _center_blocks(v0: jax.Array) -> jax.Array:
    """(B, c, D) -> (B, c, D, 1, 128) lane-replicated center blocks."""
    b, c, d = v0.shape
    return jnp.broadcast_to(v0.astype(jnp.float32)[..., None, None],
                            (b, c, d, 1, LANES))


def _center_spec(c: int, d: int):
    return pl.BlockSpec((1, c, d, 1, LANES), lambda i: (i, 0, 0, 0, 0))


def _solve_out(b: int, c: int, d: int):
    """out_specs / out_shape of the flat whole-solve kernels."""
    specs = [_center_spec(c, d), _lane_scalar_spec(), _lane_scalar_spec()]
    shapes = [jax.ShapeDtypeStruct((b, c, d, 1, LANES), jnp.float32),
              _lane_scalar_shape(b, jnp.float32),
              _lane_scalar_shape(b, jnp.int32)]
    return specs, shapes


def resident_solve_pallas(x4: jax.Array, w3: jax.Array, v0: jax.Array,
                          tol: jax.Array, m: float, max_iters: int,
                          interpret: bool = False):
    """x4 (B, D, R, 128) tiled rows, w3 (B, R, 128) row weights (0 on
    padding), v0 (B, c, D) init centers, tol (B,) per-lane stop
    tolerances -> (v (B, c, D), delta (B,), iters (B,) int32), each
    lane run to its own convergence inside one kernel launch."""
    b, d, r, _ = x4.shape
    c = v0.shape[1]
    out_specs, out_shape = _solve_out(b, c, d)
    v, delta, it = pl.pallas_call(
        partial(_resident_kernel, m=m, max_iters=max_iters),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, d, r, LANES), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, r, LANES), lambda i: (i, 0, 0)),
            _center_spec(c, d),
            _lane_scalar_spec(),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x4, w3, _center_blocks(v0), _lane_scalars(tol))
    return v[..., 0, 0], delta[:, 0, 0], it[:, 0, 0]


# ---------------------------------------------------------------------------
# HBM-streamed whole-solve (rows beyond VMEM, centers + partials resident)
# ---------------------------------------------------------------------------

def _streamed_kernel(x_hbm, w_hbm, v0_ref, tol_ref,
                     v_ref, delta_ref, it_ref,
                     xbuf, wbuf, xsem, wsem,
                     *, m: float, max_iters: int, n_chunks: int):
    lane = pl.program_id(0)
    v0 = v0_ref[0][..., :1].astype(jnp.float32)      # (c, D, 1, 1)
    c, d = v0.shape[:2]
    chunk = xbuf.shape[2]                             # (2, D, chunk, 128)

    def copies(k, slot):
        return (pltpu.make_async_copy(
                    x_hbm.at[lane, :, pl.ds(k * chunk, chunk), :],
                    xbuf.at[slot], xsem.at[slot]),
                pltpu.make_async_copy(
                    w_hbm.at[lane, pl.ds(k * chunk, chunk), :],
                    wbuf.at[slot], wsem.at[slot]))

    def step(v):
        # Prime slot 0, then stream: start chunk k+1 into the other
        # slot while chunk k is reduced into the Eq. 3 partials. Every
        # started copy is waited exactly once (k+1 starts are gated on
        # k + 1 < n_chunks; chunk k's wait reconstructs the same
        # (ref, sem) descriptor — the documented Pallas-TPU pattern).
        for cp in copies(0, 0):
            cp.start()

        def chunk_body(k, acc):
            slot = jax.lax.rem(k, 2)
            nxt = jax.lax.rem(k + 1, 2)

            @pl.when(k + 1 < n_chunks)
            def _():
                for cp in copies(k + 1, nxt):
                    cp.start()

            for cp in copies(k, slot):
                cp.wait()
            num, den = _row_partials(xbuf[slot], wbuf[slot], v, m)
            return acc[0] + num, acc[1] + den

        num, den = jax.lax.fori_loop(
            0, n_chunks, chunk_body,
            (jnp.zeros((c, d, 1, LANES), jnp.float32),
             jnp.zeros((c, 1, 1, LANES), jnp.float32)))
        return _centers(num, den)

    _converge(step, v0, tol_ref, max_iters, v_ref, delta_ref, it_ref)


def resident_streamed_solve_pallas(x4: jax.Array, w3: jax.Array,
                                   v0: jax.Array, tol: jax.Array, m: float,
                                   max_iters: int, interpret: bool = False):
    """HBM-streamed twin of :func:`resident_solve_pallas`, same
    signature and per-lane convergence semantics. ``x4``/``w3`` must
    have ``R % STREAM_CHUNK_ROWS == 0`` (``tile_rows_batched`` pads
    with ``rows_multiple=STREAM_CHUNK_ROWS``); the row tiles stay in
    HBM and are double-buffered through a 2-slot VMEM scratch."""
    b, d, r, _ = x4.shape
    c = v0.shape[1]
    if r % STREAM_CHUNK_ROWS != 0:
        raise ValueError(f"streamed solve needs R % {STREAM_CHUNK_ROWS} "
                         f"== 0, got R={r} (pad with tile_rows_batched("
                         f"..., rows_multiple=STREAM_CHUNK_ROWS))")
    out_specs, out_shape = _solve_out(b, c, d)
    v, delta, it = pl.pallas_call(
        partial(_streamed_kernel, m=m, max_iters=max_iters,
                n_chunks=r // STREAM_CHUNK_ROWS),
        grid=(b,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            _center_spec(c, d),
            _lane_scalar_spec(),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, d, STREAM_CHUNK_ROWS, LANES), jnp.float32),
            pltpu.VMEM((2, STREAM_CHUNK_ROWS, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(x4.astype(jnp.float32), w3.astype(jnp.float32), _center_blocks(v0),
      _lane_scalars(tol))
    return v[..., 0, 0], delta[:, 0, 0], it[:, 0, 0]


# ---------------------------------------------------------------------------
# VMEM-resident FCM_S stencil whole-solve
# ---------------------------------------------------------------------------

def _shift_grid(a: jax.Array, off) -> jax.Array:
    """Zero-filled shift, out[i] = a[i - off] per axis — the VMEM-array
    face of :func:`repro.core.spatial._shift` (same border semantics)."""
    pads, slices = [], []
    for ax, o in enumerate(off):
        n = a.shape[ax]
        if o >= 0:
            pads.append((o, 0))
            slices.append(slice(0, n))
        else:
            pads.append((0, -o))
            slices.append(slice(-o, None))
    return jnp.pad(a, pads)[tuple(slices)]


def _grid_sum(a: jax.Array) -> jax.Array:
    """(c, *grid) -> (c, 1, 1): major grid axes first, then the two
    minor ones one at a time with their dims kept (Mosaic aborts on a
    keepdims reduction over both at once)."""
    while a.ndim > 3:
        a = jnp.sum(a, axis=1)
    return jnp.sum(jnp.sum(a, axis=2, keepdims=True), axis=1, keepdims=True)


def _resident_stencil_kernel(x_ref, valid_ref, v0_ref, tol_ref,
                             v_ref, delta_ref, it_ref, *, m: float,
                             alpha: float, offsets, max_iters: int):
    x = x_ref[0].astype(jnp.float32)               # (Hp, Wp) / (D, Hp, Wp)
    valid = valid_ref[0].astype(jnp.float32)
    v0 = v0_ref[0][..., :1].astype(jnp.float32)    # (c, 1, 1)
    c = v0.shape[0]

    # Iteration-invariant neighborhood fields. The validity sheet plays
    # the border role: padding pixels carry valid=0 and x=0, so shifts
    # that cross the true image edge contribute nothing — exactly the
    # zero-filled out-of-bounds semantics of core.spatial.neighbor_fields
    # (per-pixel neighbor counts included).
    xv = x * valid
    cnt = jnp.zeros_like(x)
    sx = jnp.zeros_like(x)
    for off in offsets:
        cnt = cnt + _shift_grid(valid, off)
        sx = sx + _shift_grid(xv, off)
    cnt = jnp.maximum(cnt, 1.0)
    xbar = sx / cnt
    # Eq. 3' as plain Eq. 3 on the effective pixels (the reference
    # form: the (1 + alpha) divisor folded into x_eff, not the sums).
    x_eff = (x + alpha * xbar) / (1.0 + alpha)

    def step(v):
        vb = v.reshape((c,) + (1,) * x.ndim)
        d2 = (vb - x[None]) ** 2                   # (c, *grid)
        d2v = d2 * valid[None]
        nb = jnp.zeros_like(d2)
        for off in offsets:
            nb = nb + _shift_grid(d2v, (0,) + tuple(off))
        u = membership_from_d2_tile(d2 + alpha * (nb / cnt[None]), m)
        um = (u ** m) * valid[None]
        return _grid_sum(um * x_eff[None]) / jnp.maximum(_grid_sum(um),
                                                         _D2_FLOOR)

    _converge(step, v0, tol_ref, max_iters, v_ref, delta_ref, it_ref)


def resident_stencil_solve_pallas(xpad: jax.Array, vpad: jax.Array,
                                  v0: jax.Array, tol: jax.Array, m: float,
                                  alpha: float, neighbors: int,
                                  max_iters: int, interpret: bool = False):
    """Whole-solve FCM_S: ``xpad`` (B, Hp, Wp) or (B, D, Hp, Wp) padded
    pixel grids with matching validity ``vpad`` (0 on padding; from
    ``ops.tile_grid_batched``), ``v0`` (B, c) scalar init centers,
    ``tol`` (B,) -> (v (B, c), delta (B,), iters (B,) int32). Each
    lane's complete Eq. 4'/Eq. 3' fixed point runs inside one kernel."""
    from repro.core.spatial import neighbor_offsets
    b = xpad.shape[0]
    grid_shape = xpad.shape[1:]
    c = v0.shape[1]
    offsets = neighbor_offsets(len(grid_shape), neighbors)
    gblock = (1,) + grid_shape
    gmap = (lambda i: (i,) + (0,) * len(grid_shape))
    vspec = pl.BlockSpec((1, c, 1, LANES), lambda i: (i, 0, 0, 0))
    v, delta, it = pl.pallas_call(
        partial(_resident_stencil_kernel, m=m, alpha=alpha,
                offsets=offsets, max_iters=max_iters),
        grid=(b,),
        in_specs=[pl.BlockSpec(gblock, gmap), pl.BlockSpec(gblock, gmap),
                  vspec, _lane_scalar_spec()],
        out_specs=[vspec, _lane_scalar_spec(), _lane_scalar_spec()],
        out_shape=[jax.ShapeDtypeStruct((b, c, 1, LANES), jnp.float32),
                   _lane_scalar_shape(b, jnp.float32),
                   _lane_scalar_shape(b, jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=STENCIL_VMEM_BYTES),
        interpret=interpret,
    )(xpad.astype(jnp.float32), vpad.astype(jnp.float32),
      jnp.broadcast_to(v0.astype(jnp.float32)[..., None, None],
                       (b, c, 1, LANES)), _lane_scalars(tol))
    return v[:, :, 0, 0], delta[:, 0, 0], it[:, 0, 0]
