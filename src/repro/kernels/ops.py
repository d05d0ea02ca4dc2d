"""Jitted public wrappers around the FCM Pallas kernels, plus the step
dispatch registry the solver core routes through.

Handles 1-D <-> (rows, 128) tiling, padding with validity weights, and
interpret-mode fallback on non-TPU backends (kernel bodies execute in
Python on CPU for correctness validation, per the Pallas docs).

The registry at the bottom maps a step *kind* (``"flat"`` weighted-row
update, ``"stencil"`` FCM_S update, ``"slic_assign"``) to its available
implementations (``"pallas"`` kernels here, ``"reference"`` pure-jnp),
and :func:`select_step` picks one by platform and problem shape. New
variants register a builder instead of growing per-module wrappers.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import defuzzify as KD
from . import fcm_centers as KC
from . import fcm_membership as KM
from . import fcm_resident as KR
from . import fcm_spatial as KS
from . import histogram_bin as KB
from . import slic_assign as KSL

LANES = KM.LANES


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _tile(x: jax.Array, block_rows: int):
    """(N,) -> ((M,128) pixels, (M,128) weights, N) with M % block_rows == 0."""
    n = x.shape[0]
    per_block = block_rows * LANES
    n_pad = (-n) % per_block
    xp = jnp.concatenate([x.astype(jnp.float32),
                          jnp.zeros((n_pad,), jnp.float32)])
    w = jnp.concatenate([jnp.ones((n,), jnp.float32),
                         jnp.zeros((n_pad,), jnp.float32)])
    m_rows = (n + n_pad) // LANES
    return xp.reshape(m_rows, LANES), w.reshape(m_rows, LANES), n


def tile_rows(x: jax.Array, w: jax.Array, block_rows: int):
    """Weighted analogue of :func:`_tile`: tiles pixels AND their row
    weights (histogram counts, superpixel sizes; padding weighs 0), so
    the fused-partials kernel runs weighted flat problems unchanged."""
    n = x.shape[0]
    per_block = block_rows * LANES
    n_pad = (-n) % per_block
    xp = jnp.concatenate([x.astype(jnp.float32),
                          jnp.zeros((n_pad,), jnp.float32)])
    wp = jnp.concatenate([w.astype(jnp.float32),
                          jnp.zeros((n_pad,), jnp.float32)])
    m_rows = (n + n_pad) // LANES
    return xp.reshape(m_rows, LANES), wp.reshape(m_rows, LANES)


def tile_rows_batched(feats: jax.Array, w: jax.Array,
                      rows_multiple: int = 1):
    """Batched analogue of :func:`tile_rows` for the VMEM-resident
    solve: ``(B, K, D)`` feature rows + ``(B, K)`` weights become
    ``(B, D, R, 128)`` row tiles and ``(B, R, 128)`` weights with K
    padded to a 128 multiple at zero weight (padding rows are inert in
    the weighted center step). ``rows_multiple`` additionally pads R to
    a multiple of it — the HBM-streamed solve DMAs fixed
    ``STREAM_CHUNK_ROWS``-row chunks."""
    b, k, d = feats.shape
    per = rows_multiple * LANES
    n_pad = (-k) % per
    xp = jnp.pad(feats.astype(jnp.float32), ((0, 0), (0, n_pad), (0, 0)))
    wp = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, n_pad)))
    r = (k + n_pad) // LANES
    return jnp.moveaxis(xp, -1, 1).reshape(b, d, r, LANES), \
        wp.reshape(b, r, LANES)


def tile_pixels_batched(px: jax.Array, block_rows: int = 8):
    """(B, N) flat pixel payloads -> ((B, M, 128) f32 tiles, (B, M, 128)
    validity weights) with M a ``block_rows`` multiple — the layout the
    binning and defuzzify kernels stream."""
    b, n = px.shape
    per = block_rows * LANES
    n_pad = (-n) % per
    xp = jnp.pad(px.astype(jnp.float32), ((0, 0), (0, n_pad)))
    wp = jnp.pad(jnp.ones((b, n), jnp.float32), ((0, 0), (0, n_pad)))
    m_rows = (n + n_pad) // LANES
    return xp.reshape(b, m_rows, LANES), wp.reshape(b, m_rows, LANES)


def tile_grid(img: jax.Array, block_rows: int = 64):
    """Shape-preserving analogue of :func:`_tile` for stencil kernels:
    pads a 2-D image to (Hp % block_rows == 0, Wp % 128 == 0) or a 3-D
    volume to (D, Hp % 8 == 0, Wp % 128 == 0) and returns the padded
    pixels plus matching validity weights (0 on padding)."""
    img = jnp.asarray(img, jnp.float32)
    if img.ndim == 2:
        h, w = img.shape
        pad = ((0, (-h) % block_rows), (0, (-w) % LANES))
    elif img.ndim == 3:
        _, h, w = img.shape
        pad = ((0, 0), (0, (-h) % 8), (0, (-w) % LANES))
    else:
        raise ValueError(f"tile_grid needs rank 2 or 3, got {img.shape}")
    return jnp.pad(img, pad), jnp.pad(jnp.ones(img.shape, jnp.float32), pad)


def tile_grid_batched(imgs: jax.Array, block_rows: int = 8):
    """Batched :func:`tile_grid` for the resident stencil solve: a
    stack of same-shape grids ``(B, H, W)`` / ``(B, D, H, W)`` becomes
    the padded stack plus a matching validity stack (0 on padding)."""
    imgs = jnp.asarray(imgs, jnp.float32)
    if imgs.ndim == 3:
        _, h, w = imgs.shape
        pad = ((0, 0), (0, (-h) % block_rows), (0, (-w) % LANES))
    elif imgs.ndim == 4:
        _, _, h, w = imgs.shape
        pad = ((0, 0), (0, 0), (0, (-h) % 8), (0, (-w) % LANES))
    else:
        raise ValueError(f"tile_grid_batched needs rank 3 or 4, got "
                         f"{imgs.shape}")
    return jnp.pad(imgs, pad), jnp.pad(jnp.ones(imgs.shape, jnp.float32),
                                       pad)


def tile_channels(img: jax.Array, block_rows: int = 8):
    """Channel-major analogue of :func:`tile_grid` for the SLIC kernel:
    an (H, W, D) image (or (H, W) grayscale) becomes (D, Hp, Wp) planes
    with Hp % block_rows == 0 and Wp % 128 == 0, plus a single (Hp, Wp)
    validity sheet (0 on padding) shared by every channel."""
    img = jnp.asarray(img, jnp.float32)
    if img.ndim == 2:
        img = img[:, :, None]
    if img.ndim != 3:
        raise ValueError(f"tile_channels needs (H, W[, D]), got {img.shape}")
    h, w, _ = img.shape
    pad = ((0, (-h) % block_rows), (0, (-w) % LANES), (0, 0))
    xpad = jnp.moveaxis(jnp.pad(img, pad), -1, 0)
    wpad = jnp.pad(jnp.ones((h, w), jnp.float32), pad[:2])
    return xpad, wpad


@partial(jax.jit, static_argnames=("h", "w", "gy", "gx", "sw", "block_rows",
                                   "interpret"))
def _slic_assign_impl(xpad, centers, h, w, gy, gx, sw, block_rows,
                      interpret):
    return KSL.slic_assign_pallas(xpad, centers, gy, gx, h / gy, w / gx,
                                  sw, block_rows, interpret)


def slic_assign(xpad, centers, h: int, w: int, gy: int, gx: int, sw: float,
                block_rows: int = 8, interpret=None) -> jax.Array:
    """SLIC assignment via Pallas: pre-tiled (D, Hp, Wp) planes from
    :func:`tile_channels` + (K, D+2) centers -> (Hp, Wp) int32 labels.
    ``h``/``w`` are the *unpadded* dims (they set the cell intervals)."""
    if interpret is None:
        interpret = _interpret_default()
    return _slic_assign_impl(xpad, centers, h, w, gy, gx, sw, block_rows,
                             interpret)


@partial(jax.jit, static_argnames=("m", "block_rows", "interpret"))
def _membership_impl(x, v, m, block_rows, interpret):
    x2d, _, n = _tile(x, block_rows)
    u = KM.membership_pallas(x2d, v, m, block_rows, interpret)
    c = v.shape[0]
    return u.reshape(c, -1)[:, :n]


def membership(x, v, m: float = 2.0, block_rows: int = 64,
               interpret=None) -> jax.Array:
    """Eq. 4 membership via Pallas; x (N,), v (c,) -> u (c, N)."""
    if interpret is None:
        interpret = _interpret_default()
    return _membership_impl(x, v, m, block_rows, interpret)


@partial(jax.jit, static_argnames=("m", "block_rows", "interpret"))
def _center_partials_impl(x, u, m, block_rows, interpret):
    x2d, w2d, n = _tile(x, block_rows)
    c = u.shape[0]
    pad = x2d.size - n
    u_p = jnp.concatenate(
        [u.astype(jnp.float32), jnp.zeros((c, pad), jnp.float32)], axis=1)
    u3d = u_p.reshape(c, -1, LANES)
    num, den = KC.center_partials_pallas(x2d, u3d, w2d, m, block_rows,
                                         interpret)
    return num[:, None], den          # num (c,1) matches (c,F) center layout


def center_partials(x, u, m: float = 2.0, block_rows: int = 64,
                    interpret=None):
    """Eq. 3 partial sums from materialized membership (paper-faithful)."""
    if interpret is None:
        interpret = _interpret_default()
    return _center_partials_impl(x, u, m, block_rows, interpret)


@partial(jax.jit, static_argnames=("m", "block_rows", "interpret"))
def _fused_step_impl(x, v, m, block_rows, interpret):
    x2d, w2d, n = _tile(x, block_rows)
    num, den = KC.fused_partials_pallas(x2d, w2d, v, m, block_rows, interpret)
    return num / jnp.maximum(den, 1e-12)


def fused_step(x, v, m: float = 2.0, block_rows: int = 64, interpret=None):
    """One fused v -> v' FCM iteration (single kernel launch)."""
    if interpret is None:
        interpret = _interpret_default()
    return _fused_step_impl(x, v, m, block_rows, interpret)


def fused_partials(x2d, w2d, v, m: float = 2.0, block_rows: int = 64,
                   interpret=None):
    """Raw pre-tiled partials — used by the distributed fit where the
    psum happens outside the kernel."""
    if interpret is None:
        interpret = _interpret_default()
    return KC.fused_partials_pallas(x2d, w2d, v, m, block_rows, interpret)


def spatial_partials(xpad, wpad, v, m: float = 2.0, alpha: float = 1.0,
                     neighbors: int = 4, block_rows: int = 64,
                     interpret=None):
    """Raw pre-tiled FCM_S partials (Eq. 3' numerator/denominator) from
    the fused stencil kernel; inputs from :func:`tile_grid`. 3-D volumes
    always use the 6-connected stencil."""
    if interpret is None:
        interpret = _interpret_default()
    if xpad.ndim == 2:
        return KS.spatial_partials_pallas_2d(xpad, wpad, v, m, alpha,
                                             neighbors, block_rows, interpret)
    if neighbors != 6:
        raise ValueError(f"3-D neighborhoods are 6-connected, "
                         f"got {neighbors}")
    return KS.spatial_partials_pallas_3d(xpad, wpad, v, m, alpha, interpret)


@partial(jax.jit, static_argnames=("m", "alpha", "neighbors", "block_rows",
                                   "interpret"))
def _spatial_step_impl(img, v, m, alpha, neighbors, block_rows, interpret):
    xpad, wpad = tile_grid(img, block_rows)
    num, den = spatial_partials(xpad, wpad, v, m, alpha, neighbors,
                                block_rows, interpret)
    return num / jnp.maximum((1.0 + alpha) * den, 1e-12)


def spatial_step(img, v, m: float = 2.0, alpha: float = 1.0,
                 neighbors: int = 4, block_rows: int = 64, interpret=None):
    """One fused FCM_S v -> v' iteration over a 2-D image or 3-D volume
    (stencil average + membership + center reduction, single launch)."""
    if interpret is None:
        interpret = _interpret_default()
    return _spatial_step_impl(img, v, m, alpha, neighbors, block_rows,
                              interpret)


def histogram_counts(px: jax.Array, n_bins: int = 256, block_rows: int = 8,
                     interpret=None) -> jax.Array:
    """Device-resident intensity binning: ``(N,)`` or ``(B, N)`` pixel
    values -> ``(n_bins,)`` / ``(B, n_bins)`` float32 counts via the
    Pallas one-pass binning kernel. Traceable (used inside the serving
    engine's fused route programs). Bin semantics match
    :func:`repro.core.histogram.intensity_histogram`'s clamp-to-range."""
    if interpret is None:
        interpret = _interpret_default()
    squeeze = px.ndim == 1
    if squeeze:
        px = px[None]
    # Unit-weight fast path: no validity stream (it would double the
    # kernel's input bandwidth); zero-padding lands in bin 0 and the
    # static pad count is subtracted inside histogram_bin_pallas.
    b, n = px.shape
    n_pad = (-n) % (block_rows * LANES)
    xp = jnp.pad(px.astype(jnp.float32), ((0, 0), (0, n_pad)))
    x3 = xp.reshape(b, -1, LANES)
    h = KB.histogram_bin_pallas(x3, None, n_bins, block_rows, interpret,
                                n_pad=n_pad)
    return h[0] if squeeze else h


def defuzzify_labels(x: jax.Array, v: jax.Array, block_rows: int = 64,
                     interpret=None) -> jax.Array:
    """Hard labels straight from centers — one fused O(N) argmin pass
    (Pallas on TPU for scalar features, the pure-jnp reference
    elsewhere); the ``(c, N)`` distance/membership matrix never hits
    HBM. ``x`` (N,) or (N, D), ``v`` (c,) or (c, D) -> (N,) int32."""
    if x.ndim == 2 and x.shape[-1] == 1:        # (N, 1) == scalar rows
        x = x[:, 0]
        v = v[:, 0] if v.ndim == 2 else v
    n_feat = 1 if x.ndim == 1 else x.shape[-1]
    impl = select_step("labels", n_feat=n_feat)
    return impl.build(block_rows=block_rows, interpret=interpret)(x, v)


def defuzzify_labels_batched(xs: jax.Array, v: jax.Array,
                             block_rows: int = 64, interpret=None,
                             impl: Optional[str] = None) -> jax.Array:
    """Batched fused defuzzify: ``(B, N)`` scalar pixel lanes + ``(B, c)``
    centers -> ``(B, N)`` int32 labels in one launch. ``impl`` pins a
    registry implementation (the engine's route programs resolve it at
    build time); default is platform dispatch."""
    sel = select_step("labels", prefer=impl, n_feat=1)
    if sel.name == "pallas":
        if interpret is None:
            interpret = _interpret_default()
        n = xs.shape[1]
        x3, _ = tile_pixels_batched(xs, block_rows)
        lab = KD.labels_pallas(x3, v, block_rows, interpret)
        return lab.reshape(xs.shape[0], -1)[:, :n]
    from repro.core import fcm as F
    return jax.vmap(F.labels_from_centers)(xs, v)


# ---------------------------------------------------------------------------
# Step dispatch registry (what repro.core.solver routes through)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StepImpl:
    """One registered step implementation.

    ``build(**params) -> callable`` constructs the actual step (called
    at trace time inside the solver's jitted loops); ``platforms``
    limits compiled execution (off-platform falls back to interpret
    mode for Pallas impls); ``scalar_only`` marks impls restricted to
    1-D feature rows; ``batched`` marks impls safe under ``vmap``.
    """
    kind: str
    name: str
    build: Callable[..., Callable]
    platforms: Tuple[str, ...] = ("cpu", "gpu", "tpu")
    scalar_only: bool = False
    batched: bool = True
    #: VMEM-residency bounds (None = unbounded). An impl with bounds is
    #: only eligible when the problem size is known and fits.
    max_rows: Optional[int] = None
    max_c: Optional[int] = None
    max_feat: Optional[int] = None
    #: name to dispatch to instead when the platform doesn't match
    #: (the documented off-TPU behavior of the resident whole-solve).
    fallback: Optional[str] = None

    def fits(self, n_feat: int, n_rows: Optional[int],
             c: Optional[int]) -> bool:
        if self.max_feat is not None and n_feat > self.max_feat:
            return False
        if self.max_rows is not None and (n_rows is None
                                          or n_rows > self.max_rows):
            return False
        if self.max_c is not None and (c is None or c > self.max_c):
            return False
        return True


_STEP_REGISTRY: Dict[Tuple[str, str], StepImpl] = {}


def register_step(kind: str, name: str, *, platforms=("cpu", "gpu", "tpu"),
                  scalar_only: bool = False, batched: bool = True,
                  max_rows: Optional[int] = None, max_c: Optional[int] = None,
                  max_feat: Optional[int] = None,
                  fallback: Optional[str] = None):
    """Decorator: register a step builder under (kind, name). Adding an
    FCM variant = registering its step here + a problem factory in
    ``core/solver.py`` — no new fit module."""
    def deco(build):
        _STEP_REGISTRY[(kind, name)] = StepImpl(
            kind=kind, name=name, build=build, platforms=tuple(platforms),
            scalar_only=scalar_only, batched=batched, max_rows=max_rows,
            max_c=max_c, max_feat=max_feat, fallback=fallback)
        return build
    return deco


def step_impls(kind: Optional[str] = None):
    """All registered implementations (of one kind, if given)."""
    return [impl for (k, _), impl in sorted(_STEP_REGISTRY.items())
            if kind is None or k == kind]


def select_step(kind: str, *, prefer: Optional[str] = None,
                platform: Optional[str] = None, n_feat: int = 1,
                batched: bool = False, n_rows: Optional[int] = None,
                c: Optional[int] = None) -> StepImpl:
    """Dispatch: pick the step implementation for a problem shape and
    platform. ``prefer`` forces a name; otherwise the VMEM-resident
    whole-solve wins on TPU when the problem is known to fit
    (``n_rows``/``c`` within its bounds), then its HBM-streamed variant,
    then the Pallas step kernel when eligible (right platform,
    feature-dim and vmap support), and the pure-jnp reference runs
    everywhere else. A preferred impl with a declared ``fallback``
    degrades off its platforms by walking the whole fallback chain
    (e.g. resident_streamed -> resident -> reference), skipping links
    that are themselves ineligible, and raises only when the chain is
    exhausted."""
    kinds = sorted({k for k, _ in _STEP_REGISTRY})
    if kind not in kinds:
        raise ValueError(f"unknown step kind {kind!r}; one of {kinds}")
    from repro import faults as FI
    _inj = FI.get()
    if _inj is not None:
        # Chaos hook: lets tests inject a dispatch-time launch failure
        # for a specific (kind, impl) without monkeypatching internals.
        _inj.maybe_fail("kernel", route=f"{kind}/{prefer or 'auto'}")
    if prefer is not None:
        impl = _STEP_REGISTRY.get((kind, prefer))
        if impl is None:
            names = [i.name for i in step_impls(kind)]
            raise ValueError(f"no {kind!r} step implementation named "
                             f"{prefer!r}; registered: {names}")
        if impl.scalar_only and n_feat != 1:
            raise ValueError(f"{kind}/{prefer} handles scalar (D=1) "
                             f"features only, got D={n_feat}")
        if batched and not impl.batched:
            raise ValueError(f"{kind}/{prefer} does not support batched "
                             f"(vmapped) solves")
        if not impl.fits(n_feat, n_rows, c):
            raise ValueError(
                f"{kind}/{prefer} needs a VMEM-resident problem "
                f"(rows <= {impl.max_rows}, c <= {impl.max_c}, "
                f"D <= {impl.max_feat}); got rows={n_rows}, c={c}, "
                f"D={n_feat}")
        platform = platform or jax.default_backend()
        if platform in impl.platforms or impl.fallback is None:
            # Off-platform with no declared fallback = run the Pallas
            # body in interpret mode (the documented parity-test path).
            return impl
        # Walk the fallback chain iteratively: a link that is itself
        # off-platform (without being terminal) or ineligible for this
        # problem is skipped, not an error — only an exhausted chain
        # raises. (A single forced-`prefer` recursion used to re-apply
        # the hard eligibility checks to the first link and blow up on
        # 2-hop chains like resident_streamed -> resident -> reference.)
        seen = {impl.name}
        cur = impl
        walked = []
        while cur.fallback is not None and cur.fallback not in seen:
            seen.add(cur.fallback)
            nxt = _STEP_REGISTRY.get((kind, cur.fallback))
            if nxt is None:
                break
            walked.append(nxt.name)
            eligible = (not (nxt.scalar_only and n_feat != 1)
                        and not (batched and not nxt.batched)
                        and nxt.fits(n_feat, n_rows, c))
            if eligible and (platform in nxt.platforms
                             or nxt.fallback is None):
                return nxt
            cur = nxt
        raise ValueError(
            f"{kind}/{prefer} is unavailable on platform {platform!r} "
            f"and its fallback chain {walked} has no eligible "
            f"implementation for rows={n_rows}, c={c}, D={n_feat}")
    platform = platform or jax.default_backend()
    for name in ("resident", "resident_streamed", "pallas"):
        impl = _STEP_REGISTRY.get((kind, name))
        if (impl is not None and platform in impl.platforms
                and not (impl.scalar_only and n_feat != 1)
                and not (batched and not impl.batched)
                and impl.fits(n_feat, n_rows, c)):
            return impl
    return _STEP_REGISTRY[(kind, "reference")]


def build_step(kind: str, name: str, **params) -> Callable:
    """Construct the (kind, name) step with the given problem arrays."""
    return _STEP_REGISTRY[(kind, name)].build(**params)


# -- registered implementations ---------------------------------------------
# Builders import the reference math lazily: repro.core imports this
# module lazily too, and resolving both at call time keeps the package
# import graph acyclic.

@register_step("flat", "reference")
def _flat_reference(feats, weights, m, **_):
    """Canonical pure-jnp weighted-row update (repro.core.solver)."""
    from repro.core import solver as SV
    return lambda v: SV.weighted_center_step(feats, weights, v, m)


@register_step("flat", "pallas", platforms=("tpu",), scalar_only=True,
               batched=False)
def _flat_pallas(x2d, w2d, m, block_rows=64, interpret=None, **_):
    """Fused membership+center-partials kernel over pre-tiled rows."""
    if interpret is None:
        interpret = _interpret_default()

    def step(v):
        num, den = KC.fused_partials_pallas(x2d, w2d, v[:, 0], m,
                                            block_rows, interpret)
        return (num / jnp.maximum(den, 1e-12))[:, None]
    return step


@register_step("flat", "resident", platforms=("tpu",), batched=True,
               max_rows=KR.MAX_ROWS, max_c=KR.MAX_C, max_feat=KR.MAX_FEAT,
               fallback="reference")
def _flat_resident(x4, w3, m, max_iters, interpret=None, **_):
    """The VMEM-resident whole-solve: unlike the other builders this
    returns a complete ``(v0, tol) -> (v, delta, iters)`` solver, not a
    ``v -> v'`` step — the convergence loop runs INSIDE the kernel.
    Inputs are pre-tiled by :func:`tile_rows_batched` (lanes of
    ``(D, R, 128)`` rows + ``(R, 128)`` weights)."""
    if interpret is None:
        interpret = _interpret_default()

    def solve_fn(v0, tol):
        return KR.resident_solve_pallas(x4, w3, v0, tol, m, max_iters,
                                        interpret)
    return solve_fn


@register_step("flat", "resident_streamed", platforms=("tpu",), batched=True,
               max_rows=KR.STREAM_MAX_ROWS, max_c=KR.MAX_C,
               max_feat=KR.MAX_FEAT, fallback="resident")
def _flat_resident_streamed(x4, w3, m, max_iters, interpret=None, **_):
    """HBM-streamed whole-solve: same ``(v0, tol) -> (v, delta, iters)``
    contract as ``flat/resident`` but rows stream from HBM in
    double-buffered chunks, so the bound is ``STREAM_MAX_ROWS`` (its
    wall-clock validation lives in benchmarks/roofline_report.py).
    Inputs from ``tile_rows_batched(...,
    rows_multiple=KR.STREAM_CHUNK_ROWS)``. Off-TPU the fallback chain
    degrades through ``resident`` to ``reference``."""
    if interpret is None:
        interpret = _interpret_default()

    def solve_fn(v0, tol):
        return KR.resident_streamed_solve_pallas(x4, w3, v0, tol, m,
                                                 max_iters, interpret)
    return solve_fn


@register_step("bin", "reference")
def _bin_reference(n_bins=256, **_):
    """Scatter-add binning (what ``intensity_histogram`` jits); the
    algebraic oracle for the Pallas one-pass kernel."""
    def counts(px):
        def one(p):
            idx = jnp.clip(p.astype(jnp.int32), 0, n_bins - 1)
            return jnp.zeros((n_bins,), jnp.float32).at[idx].add(1.0)
        return one(px) if px.ndim == 1 else jax.vmap(one)(px)
    return counts


@register_step("bin", "pallas", platforms=("tpu",))
def _bin_pallas(n_bins=256, block_rows=8, interpret=None, **_):
    """One-pass comparison-binning kernel over (B, M, 128) tiles."""
    return lambda px: histogram_counts(px, n_bins, block_rows, interpret)


@register_step("labels", "reference")
def _labels_reference(**_):
    """argmin-distance labels via the pure-jnp (c, N) distance matrix."""
    from repro.core import fcm as F
    return lambda x, v: F.labels_from_centers(x, v)


@register_step("labels", "pallas", platforms=("tpu",), scalar_only=True)
def _labels_pallas(block_rows=64, interpret=None, **_):
    """Fused O(N) argmin tile kernel (scalar features)."""
    if interpret is None:
        interpret = _interpret_default()

    def labels(x, v):
        x3, _ = tile_pixels_batched(x[None], block_rows)
        lab = KD.labels_pallas(x3, v[None], block_rows, interpret)
        return lab.reshape(-1)[:x.shape[0]]
    return labels


@register_step("stencil", "reference")
def _stencil_reference(img, m, alpha, neighbors, **_):
    """Pure-jnp shifted-array FCM_S step (repro.core.spatial)."""
    from repro.core import spatial as SP
    return lambda v: SP.spatial_center_step(img, v[:, 0], m, alpha,
                                            neighbors)[:, None]


@register_step("stencil", "pallas", platforms=("tpu",), batched=False)
def _stencil_pallas(xpad, wpad, m, alpha, neighbors, block_rows=64,
                    interpret=None, **_):
    """Fused stencil+membership+center-reduction kernel over a pre-tiled
    grid (inputs from :func:`tile_grid`)."""
    if interpret is None:
        interpret = _interpret_default()

    def step(v):
        num, den = spatial_partials(xpad, wpad, v[:, 0], m, alpha,
                                    neighbors, block_rows, interpret)
        return (num / jnp.maximum((1.0 + alpha) * den, 1e-12))[:, None]
    return step


@register_step("stencil", "resident", platforms=("tpu",), batched=True,
               max_rows=KR.STENCIL_MAX_PIXELS, max_c=KR.STENCIL_MAX_C,
               fallback="reference")
def _stencil_resident(xpad, vpad, m, alpha, neighbors, max_iters,
                      interpret=None, **_):
    """VMEM-resident whole-solve FCM_S: the complete Eq. 4'/Eq. 3'
    fixed point of every lane runs inside one kernel (inputs from
    :func:`tile_grid_batched`; ``max_rows`` bounds the per-lane padded
    PIXEL count of :func:`~repro.kernels.fcm_resident.stencil_pixels` —
    ``FCMProblem.n_rows`` reports it for stencil problems).
    Returns a ``(v0, tol) -> (v, delta, iters)`` solver like the other
    resident builders."""
    if interpret is None:
        interpret = _interpret_default()

    def solve_fn(v0, tol):
        return KR.resident_stencil_solve_pallas(xpad, vpad, v0, tol, m,
                                                alpha, neighbors,
                                                max_iters, interpret)
    return solve_fn


@register_step("slic_assign", "reference", batched=False)
def _slic_reference(gy, gx, sw, **_):
    """Pure-jnp 3x3-candidate SLIC assignment (repro.superpixel.slic)."""
    from repro.superpixel import slic as SL
    return lambda img, centers: SL.assign_ref(img, centers, gy, gx, sw)


@register_step("slic_assign", "pallas", platforms=("tpu",), batched=False)
def _slic_pallas(h, w, gy, gx, sw, block_rows=8, interpret=None, **_):
    """Tiled Pallas SLIC assignment (pre-tiled planes from
    :func:`tile_channels`)."""
    return lambda xpad, centers: slic_assign(xpad, centers, h, w, gy, gx,
                                             sw, block_rows, interpret)
