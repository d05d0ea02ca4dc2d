"""The unified weighted-feature FCM solver core.

Every FCM variant in this repo is the same algorithm wearing a different
feature map: the fixed point iterates ``v -> step(v)`` where ``step``
substitutes the Eq. 4 membership into the Eq. 3 weighted center update
over some set of (feature row, weight) pairs —

=============  ==========================  =====================
variant        feature rows                row weights
=============  ==========================  =====================
pixels         ``(N,)`` / ``(N, D)``       1
histogram      256 bin values              bin counts
superpixels    ``(K, D)`` mean features    pixel counts
FCM_S          the pixel grid + stencil    1 (stencil-effective)
=============  ==========================  =====================

This module owns that fixed point **once**: :class:`FCMProblem` names the
feature map, :func:`solve` runs it, and :func:`solve_batched` runs a
stacked batch of independent problems with per-lane convergence masking.
The two ``lax.while_loop`` drivers (:func:`while_centers`,
:func:`masked_while_centers`) here are the ONLY convergence loops in the
repo — the legacy ``fit_*`` entry points in :mod:`repro.core.fcm`,
``histogram``, ``spatial``, ``vector_fcm`` and ``batched`` are deprecated
thin adapters over this module, and the distributed/SLIC fixed points
drive their steps through the same loops.

Step implementations (pure-jnp reference vs the Pallas kernels) are
selected through the dispatch registry in :mod:`repro.kernels.ops` by
problem shape and platform; ``backend=`` forces a choice:

* ``"auto"``       — registry pick (Pallas on TPU where eligible,
  pure-jnp reference elsewhere),
* ``"reference"``  — pure-jnp step,
* ``"pallas"``     — Pallas kernels (interpret mode off-TPU; tests only),
* ``"staged"``     — the paper-faithful host loop: staged kernels,
  membership materialized between stages, host-side ``|u' - u|_inf``
  convergence test (what :func:`repro.core.fcm.fit_baseline` wraps),
* ``"sequential"`` — the single-core numpy comparator from
  :mod:`repro.core.sequential` (the paper's CPU baseline), so the
  paper's CPU-vs-device comparison runs from this one entry point,
* ``"resident"``   — the VMEM-resident whole-solve kernel: for flat
  problems that fit on-chip (<= 256 rows, c <= 8, D <= 8 — histogram
  and superpixel payloads) the COMPLETE convergence loop runs inside
  one ``pallas_call``, zero HBM round-trips and zero per-iteration
  dispatch. ``auto`` picks it on TPU when the problem fits; off-TPU it
  falls back to the reference step (pass ``interpret=True`` to force
  the kernel for parity testing).
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import fcm as F

_D2_FLOOR = 1e-12
_BIG = 3.4e38

BACKENDS = ("auto", "reference", "pallas", "staged", "sequential",
            "resident")


def _record_telemetry(kind: str, impl: str, n_iters: int,
                      final_delta: Optional[float] = None,
                      lane_iters=None) -> None:
    """Convergence telemetry into the process-wide obs registry: every
    solve records its iterations-to-converge (per lane for batched
    solves) and final residual, so iteration-count regressions are
    visible independently of wall time. Counters/histograms:

      solver.solves{kind,impl}        — solve() / solve_batched() calls
      solver.lanes{kind,impl}         — problems solved (B per batch)
      solver.iters{kind}              — iteration-count histogram
      solver.last_final_delta{kind}   — last center-movement residual
    """
    from repro import obs
    reg = obs.default_registry()
    reg.counter("solver.solves", kind=kind, impl=impl).inc()
    h = reg.histogram("solver.iters", edges=obs.ITER_EDGES, kind=kind)
    if lane_iters is not None:
        reg.counter("solver.lanes", kind=kind, impl=impl).inc(
            len(lane_iters))
        for it in lane_iters:
            h.record(int(it))
    else:
        reg.counter("solver.lanes", kind=kind, impl=impl).inc(1)
        h.record(int(n_iters))
    if final_delta is not None and not np.isnan(final_delta):
        reg.gauge("solver.last_final_delta", kind=kind).set(
            float(final_delta))


def warn_deprecated(old: str, new: str) -> None:
    """One-release deprecation shim for the legacy ``fit_*`` aliases."""
    warnings.warn(
        f"{old} is deprecated; build an FCMProblem and call {new} "
        f"(see README 'Migrating from the fit_* zoo')",
        DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# Problem specification
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StencilSpec:
    """FCM_S neighborhood regularization (Ahmed-style).

    ``alpha`` weighs the neighborhood penalty (0 degenerates to plain
    FCM); ``neighbors`` is the stencil arity — 4 or 8 for 2-D images,
    6 for 3-D volumes.
    """
    alpha: float = 1.0
    neighbors: int = 4


@dataclasses.dataclass(frozen=True)
class FCMProblem:
    """One weighted-feature FCM problem (or a stacked batch of them).

    ``features`` is ``(K,)`` / ``(K, D)`` weighted rows for flat
    problems, or the raw pixel grid ``(H, W)`` / ``(D, H, W)`` when
    ``stencil`` is set (FCM_S needs positions, so it cannot reduce to
    rows). With ``batch=True`` a leading lane axis is added everywhere
    and lanes are independent problems. ``weights`` are per-row
    multiplicities (``None`` = 1; stencil problems take no weights).
    ``init`` overrides the default weighted-support linspace ``v0``.
    """
    features: Any
    weights: Any = None
    c: int = 4
    m: float = 2.0
    stencil: Optional[StencilSpec] = None
    init: Any = None
    batch: bool = False

    def __post_init__(self):
        feats = jnp.asarray(self.features, jnp.float32)
        object.__setattr__(self, "features", feats)
        if self.weights is not None:
            object.__setattr__(self, "weights",
                               jnp.asarray(self.weights, jnp.float32))
        if self.init is not None:
            object.__setattr__(self, "init",
                               jnp.asarray(self.init, jnp.float32))
        lead = 1 if self.batch else 0
        if self.stencil is not None:
            if self.weights is not None:
                raise ValueError("stencil problems take no row weights "
                                 "(every grid pixel weighs 1)")
            if feats.ndim - lead not in (2, 3):
                raise ValueError(
                    f"stencil problems need a (H, W) or (D, H, W) pixel "
                    f"grid{' per lane' if self.batch else ''}, got shape "
                    f"{feats.shape}")
            ndim = feats.ndim - lead
            ok = (4, 8) if ndim == 2 else (6,)
            if self.stencil.neighbors not in ok:
                raise ValueError(
                    f"{ndim}-D neighborhoods are "
                    f"{' or '.join(map(str, ok))}-connected, got "
                    f"{self.stencil.neighbors}")
        else:
            if feats.ndim - lead not in (1, 2):
                raise ValueError(
                    f"flat problems need (K,) or (K, D) feature rows"
                    f"{' per lane' if self.batch else ''}, got shape "
                    f"{feats.shape}")

    # -- shape helpers -----------------------------------------------------

    @property
    def scalar(self) -> bool:
        """True when centers should come back featureless, shape (c,)."""
        lead = 1 if self.batch else 0
        if self.stencil is not None:
            return True
        return self.features.ndim - lead == 1

    @property
    def n_feat(self) -> int:
        if self.scalar:
            return 1
        return self.features.shape[-1]

    @property
    def n_rows(self) -> Optional[int]:
        """Problem size the registry's VMEM-residency bounds are
        checked against: the row count of a flat problem, or the
        per-lane padded PIXEL count of a stencil problem (what the
        resident stencil solve must hold in VMEM)."""
        lead = 1 if self.batch else 0
        if self.stencil is not None:
            from repro.kernels.fcm_resident import stencil_pixels
            return stencil_pixels(self.features.shape[lead:])
        return int(self.features.shape[lead])

    def rows(self) -> Tuple[jax.Array, jax.Array]:
        """Canonical ``(K, D)`` rows + ``(K,)`` weights (flat problems;
        with ``batch=True`` a leading lane axis on both)."""
        if self.stencil is not None:
            raise ValueError("stencil problems have no flat rows")
        feats = self.features
        lead = 1 if self.batch else 0
        if feats.ndim - lead == 1:
            feats = feats[..., None]
        w = self.weights
        if w is None:
            w = jnp.ones(feats.shape[:-1], jnp.float32)
        return feats, w


# -- problem factories (what the deprecated fit_* adapters build) -----------

def _cfg_c_m(cfg, c, m):
    if cfg is not None:
        c = cfg.n_clusters if c is None else c
        m = cfg.m if m is None else m
    return (4 if c is None else int(c)), (2.0 if m is None else float(m))


def pixel_problem(x, cfg: Optional[F.FCMConfig] = None, *,
                  c: Optional[int] = None, m: Optional[float] = None,
                  v0=None) -> FCMProblem:
    """Uncompressed pixels (the paper's problem): ``x`` is ``(N,)``
    grayscale or ``(N, D)`` feature rows, every row weighing 1."""
    c, m = _cfg_c_m(cfg, c, m)
    return FCMProblem(features=x, c=c, m=m, init=v0)


def histogram_problem(x=None, cfg: Optional[F.FCMConfig] = None, *,
                      hist=None, n_bins: int = 256,
                      c: Optional[int] = None, m: Optional[float] = None,
                      v0=None) -> FCMProblem:
    """Histogram-compressed scalar FCM: ``n_bins`` (value, count) rows.
    Pass pixels ``x`` (histogrammed on ingest) or a prebuilt ``hist``."""
    from . import histogram as H
    c, m = _cfg_c_m(cfg, c, m)
    if hist is None:
        if x is None:
            raise ValueError("histogram_problem needs pixels x or a hist")
        hist = H.intensity_histogram(jnp.asarray(x, jnp.float32), n_bins)
    vals = jnp.arange(n_bins, dtype=jnp.float32)
    return FCMProblem(features=vals, weights=hist, c=c, m=m, init=v0)


def vector_problem(feats, weights=None, cfg: Optional[F.FCMConfig] = None, *,
                   c: Optional[int] = None, m: Optional[float] = None,
                   v0=None) -> FCMProblem:
    """Weighted vector rows (the superpixel-compression payload)."""
    c, m = _cfg_c_m(cfg, c, m)
    return FCMProblem(features=feats, weights=weights, c=c, m=m, init=v0)


def spatial_problem(img, cfg=None, *, alpha: Optional[float] = None,
                    neighbors: Optional[int] = None,
                    c: Optional[int] = None, m: Optional[float] = None,
                    v0=None) -> FCMProblem:
    """FCM_S over a 2-D image or 3-D volume. ``cfg`` may be a
    :class:`repro.core.spatial.SpatialFCMConfig` (supplies
    alpha/neighbors too); 3-D volumes always use the 6-stencil."""
    c, m = _cfg_c_m(cfg, c, m)
    if alpha is None:
        alpha = getattr(cfg, "alpha", 1.0)
    if neighbors is None:
        neighbors = getattr(cfg, "neighbors", 4)
    img = jnp.asarray(img, jnp.float32)
    if img.ndim == 3:
        neighbors = 6
    return FCMProblem(features=img, c=c, m=m,
                      stencil=StencilSpec(alpha=float(alpha),
                                          neighbors=int(neighbors)),
                      init=v0)


def batch_problems(features, weights=None, *, stencil=None,
                   cfg: Optional[F.FCMConfig] = None,
                   c: Optional[int] = None,
                   m: Optional[float] = None) -> FCMProblem:
    """Stack same-shape independent problems along a leading lane axis:
    flat ``(B, K[, D])`` rows (+ ``(B, K)`` weights) or stencil
    ``(B, H, W)`` / ``(B, D, H, W)`` grids."""
    c, m = _cfg_c_m(cfg, c, m)
    return FCMProblem(features=features, weights=weights, c=c, m=m,
                      stencil=stencil, batch=True)


# ---------------------------------------------------------------------------
# The canonical center update and convergence loops
# ---------------------------------------------------------------------------

def weighted_center_step(feats: jax.Array, w: jax.Array, v: jax.Array,
                         m: float) -> jax.Array:
    """THE core update: one fused ``v -> v'`` step of weighted FCM.

    Eq. 4 membership on the rows substituted into the weighted Eq. 3
    center update; memberships never leave the step. ``feats`` ``(K,)``
    or ``(K, D)``, ``w`` ``(K,)`` (zero rows are inert), ``v`` ``(c, D)``
    -> ``(c, D)``. With unit weights and scalar rows this is bitwise
    :func:`repro.core.fcm.fused_center_step`.
    """
    feats2 = F._as_2d(feats)
    u = F.update_membership(feats2, v, m)                 # (c, K)
    um = (u ** m) * w[None, :]
    # broadcast-multiply-sum rather than `um @ feats2`: the reduction
    # order matches fcm.update_centers bitwise, which is what keeps the
    # unit-weight case (and FCM_S at alpha=0, which goes through
    # update_centers) iteration-for-iteration identical to this step —
    # the parity the adapter tests pin. XLA fuses the product into the
    # reduction, and with c ~ 4 the matmul would not be MXU-bound anyway.
    num = jnp.sum(um[:, :, None] * feats2[None, :, :], axis=1)
    den = jnp.maximum(jnp.sum(um, axis=1)[:, None], _D2_FLOOR)
    return num / den


def while_centers(step, v0, tol, max_iters):
    """Device-resident center fixed point: iterate ``v -> step(v)`` until
    ``max|v' - v| < tol`` or ``max_iters``. Returns ``(v, delta, it)``.

    This (plus :func:`masked_while_centers`) is the only FCM convergence
    loop in the repo; every variant's trajectory is defined by it.
    """
    def cond(state):
        _, delta, it = state
        return jnp.logical_and(delta >= tol, it < max_iters)

    def body(state):
        v, _, it = state
        v_new = step(v)
        delta = jnp.max(jnp.abs(v_new - v))
        return v_new, delta, it + 1

    state = (jnp.asarray(v0, jnp.float32),
             jnp.asarray(jnp.inf, jnp.float32),
             jnp.asarray(0, jnp.int32))
    return jax.lax.while_loop(cond, body, state)


def masked_while_centers(step, v0, tol, max_iters, active=None):
    """Per-lane-masked batched fixed point: run ``v' = step(v)``
    (``(B, cd) -> (B, cd)``) until every lane's ``max|v' - v| < tol[b]``
    or ``max_iters``, inside ONE while_loop. Converged lanes freeze
    (centers verbatim, iteration counters stop), so each lane's
    trajectory is identical to a solo :func:`while_centers` run.

    ``active`` is an optional ``(B,)`` bool mask naming the *real*
    lanes: inactive lanes (batch padding up to a bucket or mesh size)
    start frozen — they keep ``v0`` verbatim, report 0 iterations and a
    0.0 residual, and can neither stretch the loop's trip count nor
    perturb any convergence statistic. ``None`` means every lane is
    real (the pre-existing behavior, bitwise).

    Returns ``(v, delta (B,), iters (B,), total_it)``."""
    b = v0.shape[0]

    def cond(state):
        _, _, _, done, it = state
        return jnp.logical_and(jnp.logical_not(jnp.all(done)), it < max_iters)

    def body(state):
        v, delta, iters, done, it = state
        v_new = step(v)
        v_new = jnp.where(done[:, None], v, v_new)
        d = jnp.max(jnp.abs(v_new - v), axis=1)
        delta = jnp.where(done, delta, d)
        iters = iters + jnp.where(done, 0, 1).astype(jnp.int32)
        done = jnp.logical_or(done, d < tol)
        return v_new, delta, iters, done, it + 1

    if active is None:
        done0 = jnp.zeros((b,), bool)
        delta0 = jnp.full((b,), jnp.inf, jnp.float32)
    else:
        done0 = jnp.logical_not(jnp.asarray(active, bool))
        delta0 = jnp.where(done0, 0.0, jnp.inf).astype(jnp.float32)
    state = (v0,
             delta0,
             jnp.zeros((b,), jnp.int32),
             done0,
             jnp.asarray(0, jnp.int32))
    v, delta, iters, done, it = jax.lax.while_loop(cond, body, state)
    return v, delta, iters, it


# ---------------------------------------------------------------------------
# Init + tolerance from the weighted feature support
# ---------------------------------------------------------------------------

def weighted_support(feats2: jax.Array, w: jax.Array):
    """Per-dimension (lo, hi) over rows with nonzero weight — empty
    superpixels, zero histogram bins and batch padding must stretch
    neither the init nor the tolerance. ``(K, D)``, ``(K,)`` -> (D,) x2."""
    active = (w > 0)[:, None]
    lo = jnp.min(jnp.where(active, feats2, _BIG), axis=0)
    hi = jnp.max(jnp.where(active, feats2, -_BIG), axis=0)
    return lo, hi


def linspace_from_support(lo: jax.Array, hi: jax.Array, c: int) -> jax.Array:
    """lo/hi (..., D) -> per-dimension linspace centers (..., c, D)."""
    frac = (jnp.arange(c, dtype=lo.dtype) + 0.5) / c
    shape = (1,) * (lo.ndim - 1) + (c, 1)
    return lo[..., None, :] + frac.reshape(shape) * (hi - lo)[..., None, :]


def _tol_from_range(rng, eps):
    """Center-movement tolerance: the membership test at eps corresponds
    to a center test at ~eps * data-range (Lipschitz); scaled by 0.1."""
    return eps * jnp.where(rng > 0, rng, 1.0) * 0.1


@partial(jax.jit, static_argnames=("b",))
def _lane_tol_stencil(features, eps, b):
    flat = features.reshape(b, -1)
    rng = jnp.max(flat, axis=1) - jnp.min(flat, axis=1)
    return _tol_from_range(rng, eps)


@jax.jit
def _lane_tol_flat(feats, w, eps):
    lo, hi = jax.vmap(weighted_support)(feats, w)
    return _tol_from_range(jnp.max(hi - lo, axis=1), eps)


def lane_tolerances(problem: FCMProblem, eps: float) -> np.ndarray:
    """Host-side replica of the per-lane center-movement tolerances the
    batched loop drivers derive internally (same f32 arithmetic), so a
    post-solve pass can decide per lane whether ``final_delta`` actually
    met the stop test — the ``converged`` signal on
    :class:`BatchedFCMResult`. Jitted per shape: this runs on every
    ``solve_batched`` call, so eager dispatch here would tax the B=64
    hot path the throughput gate times."""
    if problem.stencil is not None:
        b = problem.features.shape[0]
        return np.asarray(_lane_tol_stencil(problem.features, eps, b))
    feats, w = problem.rows()
    return np.asarray(_lane_tol_flat(feats, w, eps))


def _single_init(problem: FCMProblem, eps: float, tol: Optional[float]):
    """Concrete (v0 (c, D), tol) for one problem (eager, like fit_*)."""
    if problem.stencil is not None:
        flat = problem.features.reshape(-1, 1)
        w = jnp.ones((flat.shape[0],), jnp.float32)
    else:
        flat, w = problem.rows()
    lo, hi = weighted_support(flat, w)
    if problem.init is not None:
        v0 = F._as_2d(problem.init)
    else:
        v0 = linspace_from_support(lo, hi, problem.c)
    if tol is None:
        # Same formula (and f32 arithmetic) as the batched per-lane
        # tolerances, so a lane's trajectory matches its solo solve.
        tol = float(_tol_from_range(jnp.max(hi - lo), eps))
    return v0, tol


# ---------------------------------------------------------------------------
# Jitted loop drivers (one per step kind x impl; stable jit signatures)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("c", "m", "max_iters"))
def _flat_loop(feats2, w, v0, c, m, tol, max_iters):
    from repro.kernels import ops as kops
    step = kops.build_step("flat", "reference", feats=feats2, weights=w, m=m)
    return while_centers(step, v0, tol, max_iters)


@partial(jax.jit, static_argnames=("c", "m", "max_iters", "block_rows",
                                   "interpret"))
def _flat_loop_pallas(x2d, w2d, v0, c, m, tol, max_iters, block_rows,
                      interpret):
    from repro.kernels import ops as kops
    step = kops.build_step("flat", "pallas", x2d=x2d, w2d=w2d, m=m,
                           block_rows=block_rows, interpret=interpret)
    return while_centers(step, v0, tol, max_iters)


@partial(jax.jit, static_argnames=("c", "m", "max_iters", "interpret"))
def _flat_loop_resident(x4, w3, v0, c, m, tol, max_iters, interpret):
    """Single-problem face of the VMEM-resident whole-solve kernel
    (one lane); returns the same (v, delta, it) triple as the other
    loop drivers."""
    from repro.kernels import ops as kops
    solve_fn = kops.build_step("flat", "resident", x4=x4, w3=w3, m=m,
                               max_iters=max_iters, interpret=interpret)
    v, delta, it = solve_fn(v0[None], jnp.asarray(tol, jnp.float32)[None])
    return v[0], delta[0], it[0]


@partial(jax.jit, static_argnames=("m", "alpha", "neighbors", "max_iters"))
def _stencil_loop(img, v0, m, alpha, neighbors, tol, max_iters):
    from repro.kernels import ops as kops
    step = kops.build_step("stencil", "reference", img=img, m=m,
                           alpha=alpha, neighbors=neighbors)
    return while_centers(step, v0, tol, max_iters)


@partial(jax.jit, static_argnames=("c", "m", "max_iters", "interpret"))
def _flat_loop_resident_streamed(x4, w3, v0, c, m, tol, max_iters,
                                 interpret):
    """Single-problem face of the HBM-streamed whole-solve kernel
    (inputs pre-tiled with ``rows_multiple=STREAM_CHUNK_ROWS``)."""
    from repro.kernels import ops as kops
    solve_fn = kops.build_step("flat", "resident_streamed", x4=x4, w3=w3,
                               m=m, max_iters=max_iters,
                               interpret=interpret)
    v, delta, it = solve_fn(v0[None], jnp.asarray(tol, jnp.float32)[None])
    return v[0], delta[0], it[0]


@partial(jax.jit, static_argnames=("m", "alpha", "neighbors", "max_iters",
                                   "block_rows", "interpret"))
def _stencil_loop_pallas(xpad, wpad, v0, m, alpha, neighbors, tol,
                         max_iters, block_rows, interpret):
    from repro.kernels import ops as kops
    step = kops.build_step("stencil", "pallas", xpad=xpad, wpad=wpad, m=m,
                           alpha=alpha, neighbors=neighbors,
                           block_rows=block_rows, interpret=interpret)
    return while_centers(step, v0, tol, max_iters)


@partial(jax.jit, static_argnames=("m", "alpha", "neighbors", "max_iters",
                                   "interpret"))
def _stencil_loop_resident(xpad, vpad, v0, m, alpha, neighbors, tol,
                           max_iters, interpret):
    """Single-problem face of the VMEM-resident FCM_S whole-solve
    (one lane; inputs from ``tile_grid_batched``). Returns the same
    ``(v (c, 1), delta, it)`` triple as the other stencil drivers."""
    from repro.kernels import ops as kops
    solve_fn = kops.build_step("stencil", "resident", xpad=xpad, vpad=vpad,
                               m=m, alpha=alpha, neighbors=neighbors,
                               max_iters=max_iters, interpret=interpret)
    v, delta, it = solve_fn(v0[None, :, 0],
                            jnp.asarray(tol, jnp.float32)[None])
    return v[0][:, None], delta[0], it[0]


def flat_batched_solve(feats, w, c, m, eps, max_iters,
                       impl: str = "reference", interpret: bool = False,
                       active=None):
    """Traceable batched flat solve: feats (B, K, D), w (B, K) ->
    (v (B, c, D), delta (B,), iters (B,), total). The core both jitted
    loop drivers wrap, exported un-jitted so larger device programs
    (the serving engine's fused route programs) can inline it and keep a
    whole request batch at ONE dispatch. ``impl`` picks the registry
    implementation: ``"reference"`` is the per-lane-masked vmapped
    ``while_loop``; ``"resident"`` / ``"resident_streamed"`` run every
    lane's complete convergence loop inside one whole-solve kernel
    (VMEM-held vs HBM-streamed rows; each lane stops at its own
    convergence point, so trajectories match solo solves either
    way). ``active`` is the optional (B,) real-lane mask of
    :func:`masked_while_centers` — padding lanes freeze at iteration 0
    (reference impl only; the whole-solve kernels have no lane mask)."""
    from repro.kernels import ops as kops
    from repro.kernels import fcm_resident as KR
    b, _, d = feats.shape
    lo, hi = jax.vmap(weighted_support)(feats, w)           # (B, D) each
    v0 = linspace_from_support(lo, hi, c)                   # (B, c, D)
    tol = _tol_from_range(jnp.max(hi - lo, axis=1), eps)

    if impl in ("resident", "resident_streamed"):
        if active is not None:
            raise ValueError("active lane masks are supported by the "
                             "reference impl only (the whole-solve "
                             "kernels run every lane)")
        rows_multiple = (KR.STREAM_CHUNK_ROWS
                         if impl == "resident_streamed" else 1)
        x4, w3 = kops.tile_rows_batched(feats, w,
                                        rows_multiple=rows_multiple)
        solve_fn = kops.build_step("flat", impl, x4=x4, w3=w3, m=m,
                                   max_iters=max_iters, interpret=interpret)
        v, delta, iters = solve_fn(v0, tol)
        return v, delta, iters, jnp.max(iters)

    vstep = jax.vmap(weighted_center_step, in_axes=(0, 0, 0, None))

    def flat_step(vflat):
        return vstep(feats, w, vflat.reshape(b, c, d), m).reshape(b, c * d)

    v, delta, iters, it = masked_while_centers(
        flat_step, v0.reshape(b, c * d), tol, max_iters, active=active)
    return v.reshape(b, c, d), delta, iters, it


@partial(jax.jit, static_argnames=("c", "m", "max_iters"))
def _flat_batched_loop(feats, w, c, m, eps, max_iters):
    """feats (B, K, D), w (B, K) -> (v (B, c, D), delta, iters, total)."""
    return flat_batched_solve(feats, w, c, m, eps, max_iters)


@partial(jax.jit, static_argnames=("c", "m", "max_iters"))
def _flat_batched_loop_masked(feats, w, active, c, m, eps, max_iters):
    """Ragged-batch twin of :func:`_flat_batched_loop`: ``active`` (B,)
    bool freezes padding lanes at iteration 0 so they can't perturb the
    shared-loop trip count (the real lanes' iters/delta/total match an
    unpadded solve exactly)."""
    return flat_batched_solve(feats, w, c, m, eps, max_iters,
                              active=active)


@partial(jax.jit, static_argnames=("c", "m", "max_iters", "interpret"))
def _flat_batched_loop_resident(feats, w, c, m, eps, max_iters, interpret):
    """Whole-solve-kernel twin of :func:`_flat_batched_loop`: one
    ``pallas_call`` runs every lane to its own convergence."""
    return flat_batched_solve(feats, w, c, m, eps, max_iters,
                              impl="resident", interpret=interpret)


@partial(jax.jit, static_argnames=("c", "m", "max_iters", "interpret"))
def _flat_batched_loop_resident_streamed(feats, w, c, m, eps, max_iters,
                                         interpret):
    """HBM-streamed twin of :func:`_flat_batched_loop_resident` for
    lanes whose rows exceed the VMEM-held bound."""
    return flat_batched_solve(feats, w, c, m, eps, max_iters,
                              impl="resident_streamed",
                              interpret=interpret)


def stencil_batched_solve(imgs, c, m, alpha, neighbors, eps, max_iters,
                          impl: str = "reference",
                          interpret: bool = False):
    """Traceable batched FCM_S solve: imgs (B, *grid) -> (v (B, c),
    delta, iters, total) — the stencil twin of
    :func:`flat_batched_solve`, exported un-jitted so the serving
    engine's fused spatial route program can inline it. ``impl``:
    ``"reference"`` vmaps the shifted-array stencil step through the
    per-lane-masked ``while_loop``; ``"resident"`` runs every lane's
    complete fixed point inside one whole-solve stencil kernel."""
    from . import spatial as SP
    b = imgs.shape[0]
    flat = imgs.reshape(b, -1)
    lo = jnp.min(flat, axis=1)
    hi = jnp.max(flat, axis=1)
    frac = (jnp.arange(c, dtype=jnp.float32) + 0.5) / c
    v0 = lo[:, None] + frac[None, :] * (hi - lo)[:, None]
    tol = _tol_from_range(hi - lo, eps)

    if impl == "resident":
        from repro.kernels import ops as kops
        xpad, vpad = kops.tile_grid_batched(imgs)
        solve_fn = kops.build_step("stencil", "resident", xpad=xpad,
                                   vpad=vpad, m=m, alpha=alpha,
                                   neighbors=neighbors,
                                   max_iters=max_iters, interpret=interpret)
        v, delta, iters = solve_fn(v0, tol)
        return v, delta, iters, jnp.max(iters)

    vstep = jax.vmap(SP.spatial_center_step, in_axes=(0, 0, None, None, None))

    def step(v):
        return vstep(imgs, v, m, alpha, neighbors)

    return masked_while_centers(step, v0, tol, max_iters)


@partial(jax.jit, static_argnames=("c", "m", "alpha", "neighbors",
                                   "max_iters"))
def _stencil_batched_loop(imgs, c, m, alpha, neighbors, eps, max_iters):
    """imgs (B, *grid) -> (v (B, c), delta, iters, total). The batched
    FCM_S path: same per-lane masking as the flat batch, stencil step
    vmapped over lanes — what makes spatial serving traffic batchable."""
    return stencil_batched_solve(imgs, c, m, alpha, neighbors, eps,
                                 max_iters)


@partial(jax.jit, static_argnames=("c", "m", "alpha", "neighbors",
                                   "max_iters", "interpret"))
def _stencil_batched_loop_resident(imgs, c, m, alpha, neighbors, eps,
                                   max_iters, interpret):
    """Whole-solve-kernel twin of :func:`_stencil_batched_loop`: one
    ``pallas_call`` runs every lane's FCM_S fixed point."""
    return stencil_batched_solve(imgs, c, m, alpha, neighbors, eps,
                                 max_iters, impl="resident",
                                 interpret=interpret)


# ---------------------------------------------------------------------------
# solve / solve_batched
# ---------------------------------------------------------------------------

def _resolve(cfg, eps, max_iters, seed=0):
    if eps is None:
        eps = cfg.eps if cfg is not None else F.FCMConfig.eps
    if max_iters is None:
        max_iters = cfg.max_iters if cfg is not None else F.FCMConfig.max_iters
    if seed is None:
        seed = cfg.seed if cfg is not None else F.FCMConfig.seed
    return float(eps), int(max_iters), int(seed)


def _select_impl(problem: FCMProblem, backend: str, batch: bool = False,
                 force_platform: Optional[str] = None) -> str:
    """Registry dispatch: which step implementation runs this problem.
    ``force_platform`` overrides the platform check (``interpret=True``
    forces the resident kernel off-TPU for parity testing).
    ``backend="resident"`` routes by problem size: the VMEM-held
    whole-solve when the rows fit its bounds, the HBM-streamed variant
    for larger flat problems, the resident stencil solve for stencil
    problems."""
    from repro.kernels import ops as kops
    prefer = {"auto": None, "reference": "reference",
              "pallas": "pallas", "resident": "resident"}[backend]
    kind = "stencil" if problem.stencil is not None else "flat"
    if backend == "resident" and kind == "flat":
        small = kops._STEP_REGISTRY[("flat", "resident")]
        if not small.fits(problem.n_feat, problem.n_rows, problem.c):
            prefer = "resident_streamed"
    impl = kops.select_step(kind, prefer=prefer, platform=force_platform,
                            n_feat=problem.n_feat, batched=batch,
                            n_rows=problem.n_rows, c=problem.c)
    return impl.name


def solve(problem: FCMProblem, cfg: Optional[F.FCMConfig] = None, *,
          eps: Optional[float] = None, max_iters: Optional[int] = None,
          tol: Optional[float] = None, backend: str = "auto",
          keep_membership: bool = False, u0=None,
          seed: Optional[int] = None,
          block_rows: int = 64, interpret: Optional[bool] = None
          ) -> F.FCMResult:
    """Solve one :class:`FCMProblem` to convergence.

    ``eps``/``max_iters``/``seed`` (or a legacy
    :class:`~repro.core.fcm.FCMConfig` supplying them) control the stop
    test and the random-init backends: the center-movement tolerance is
    ``eps * feature-range * 0.1`` unless an absolute ``tol`` is given
    (``tol=-1`` forces exactly ``max_iters`` iterations — what the
    benchmarks use for like-for-like timing); ``seed`` only matters for
    the membership-initialized ``staged``/``sequential`` backends.
    ``labels`` come back per-row for flat problems and grid-shaped for
    stencil problems.
    """
    if problem.batch:
        raise ValueError("solve() takes a single problem; use "
                         "solve_batched() for batch=True problems")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    eps, max_iters, seed = _resolve(cfg, eps, max_iters, seed)

    if backend == "sequential":
        res = _solve_sequential(problem, eps, max_iters, seed, u0)
        _record_telemetry("flat", "sequential", res.n_iters,
                          res.final_delta)
        return res
    if backend == "staged":
        res = solve_staged(problem, eps=eps, max_iters=max_iters,
                           seed=seed, u0=u0,
                           keep_membership=keep_membership)
        _record_telemetry("flat", "staged", res.n_iters, res.final_delta)
        return res

    # interpret=True forces Pallas-family impls off-platform (tests);
    # without it backend="resident" degrades to the reference step
    # off-TPU, per the registry's declared fallback.
    force = "tpu" if (backend == "resident" and interpret) else None
    impl = _select_impl(problem, backend, force_platform=force)
    v0, tol = _single_init(problem, eps, tol)
    c, m = problem.c, problem.m

    if problem.stencil is not None:
        img = problem.features
        alpha, neighbors = problem.stencil.alpha, problem.stencil.neighbors
        if impl == "pallas":
            from repro.kernels import ops as kops
            xpad, wpad = kops.tile_grid(img, block_rows)
            if interpret is None:
                interpret = kops._interpret_default()
            v, delta, it = _stencil_loop_pallas(
                xpad, wpad, v0, m, alpha, neighbors, tol, max_iters,
                block_rows, interpret)
        elif impl == "resident":
            from repro.kernels import ops as kops
            xpad, vpad = kops.tile_grid_batched(img[None])
            if interpret is None:
                interpret = kops._interpret_default()
            v, delta, it = _stencil_loop_resident(
                xpad, vpad, v0, m, alpha, neighbors, tol, max_iters,
                interpret)
        else:
            v, delta, it = _stencil_loop(img, v0, m, alpha, neighbors,
                                         tol, max_iters)
        from . import spatial as SP
        u = SP.spatial_membership(img, v[:, 0], m, alpha, neighbors)
        labels = F.defuzzify(u.reshape(c, -1)).reshape(img.shape)
        _record_telemetry("stencil", impl, int(it), float(delta))
        centers = v[:, 0]
        return F.FCMResult(centers=centers, labels=labels, n_iters=int(it),
                           final_delta=float(delta),
                           membership=u if keep_membership else None,
                           converged=bool(float(delta) < tol),
                           healthy=bool(np.isfinite(
                               np.asarray(centers)).all()))

    feats2, w = problem.rows()
    if impl == "resident":
        from repro.kernels import ops as kops
        x4, w3 = kops.tile_rows_batched(feats2[None], w[None])
        if interpret is None:
            interpret = kops._interpret_default()
        v, delta, it = _flat_loop_resident(x4, w3, v0, c, m, tol,
                                           max_iters, interpret)
    elif impl == "resident_streamed":
        from repro.kernels import ops as kops
        from repro.kernels import fcm_resident as KR
        x4, w3 = kops.tile_rows_batched(
            feats2[None], w[None], rows_multiple=KR.STREAM_CHUNK_ROWS)
        if interpret is None:
            interpret = kops._interpret_default()
        v, delta, it = _flat_loop_resident_streamed(
            x4, w3, v0, c, m, tol, max_iters, interpret)
    elif impl == "pallas":
        from repro.kernels import ops as kops
        x2d, w2d = kops.tile_rows(feats2[:, 0], w, block_rows)
        if interpret is None:
            interpret = kops._interpret_default()
        v, delta, it = _flat_loop_pallas(x2d, w2d, v0, c, m, tol,
                                         max_iters, block_rows, interpret)
    else:
        v, delta, it = _flat_loop(feats2, w, v0, c, m, tol, max_iters)
    from repro.kernels import ops as kops
    labels = kops.defuzzify_labels(feats2, v, interpret=interpret)
    u = F.update_membership(feats2, v, m) if keep_membership else None
    centers = v[:, 0] if problem.scalar else v
    _record_telemetry("flat", impl, int(it), float(delta))
    return F.FCMResult(centers=centers, labels=labels, n_iters=int(it),
                       final_delta=float(delta), membership=u,
                       converged=bool(float(delta) < tol),
                       healthy=bool(np.isfinite(np.asarray(centers)).all()))


@dataclasses.dataclass
class BatchedFCMResult:
    """Per-lane results of a batched solve (+ per-lane health flags)."""
    centers: jax.Array            # (B, c) scalar or (B, c, D)
    n_iters: np.ndarray           # (B,) int32, per-lane iteration counts
    final_delta: np.ndarray       # (B,) float32, per-lane last center move
    total_iters: int              # global while_loop trip count
    labels: Optional[list] = None  # per lane, if the adapter computes them
    #: (B,) bool — lane met its center-movement tolerance (False =
    #: max_iters exhausted). None only on legacy constructors.
    converged: Optional[np.ndarray] = None
    #: (B,) bool — lane's centers are all finite (post-salvage).
    healthy: Optional[np.ndarray] = None
    #: (B,) bool — lane was re-solved on the reference backend after the
    #: primary impl left it poisoned/unconverged.
    salvaged: Optional[np.ndarray] = None


def solve_batched(problem: FCMProblem, cfg: Optional[F.FCMConfig] = None, *,
                  eps: Optional[float] = None,
                  max_iters: Optional[int] = None,
                  backend: str = "auto",
                  interpret: Optional[bool] = None,
                  salvage: bool = True) -> BatchedFCMResult:
    """Solve a stacked batch of independent problems (``batch=True``):
    one device loop — the per-lane-masked reference ``while_loop``, or
    the VMEM-resident whole-solve kernel (``backend="resident"``, or
    ``auto`` on TPU when the problem fits) — with each lane freezing at
    its own convergence point, so a lane's trajectory is identical to
    what :func:`solve` would produce for it alone.

    Post-solve, every lane gets health flags (``converged`` — met its
    tolerance; ``healthy`` — finite centers), and with ``salvage=True``
    (the default) bad lanes are re-solved *individually-masked* on the
    reference loop and scattered back — one poisoned or kernel-diverged
    lane degrades to the reference backend instead of failing the whole
    batch, and healthy lanes' centers ride through bitwise untouched."""
    if not problem.batch:
        raise ValueError("solve_batched() needs a batch=True problem "
                         "(see batch_problems())")
    if backend not in ("auto", "reference", "resident"):
        raise ValueError(f"batched solves run the reference (vmapped) or "
                         f"resident steps only; got backend={backend!r}")
    eps, max_iters, _ = _resolve(cfg, eps, max_iters)
    force = "tpu" if (backend == "resident" and interpret) else None
    impl = _select_impl(problem, backend, batch=True, force_platform=force)
    c, m = problem.c, problem.m

    if problem.stencil is not None:
        if impl == "resident":
            from repro.kernels import ops as kops
            if interpret is None:
                interpret = kops._interpret_default()
            v, delta, iters, it = _stencil_batched_loop_resident(
                problem.features, c, m, problem.stencil.alpha,
                problem.stencil.neighbors, eps, max_iters, interpret)
        else:
            v, delta, iters, it = _stencil_batched_loop(
                problem.features, c, m, problem.stencil.alpha,
                problem.stencil.neighbors, eps, max_iters)
    else:
        feats, w = problem.rows()
        if impl in ("resident", "resident_streamed"):
            from repro.kernels import ops as kops
            if interpret is None:
                interpret = kops._interpret_default()
            if impl == "resident":
                v, delta, iters, it = _flat_batched_loop_resident(
                    feats, w, c, m, eps, max_iters, interpret)
            else:
                v, delta, iters, it = _flat_batched_loop_resident_streamed(
                    feats, w, c, m, eps, max_iters, interpret)
        else:
            v, delta, iters, it = _flat_batched_loop(feats, w, c, m, eps,
                                                     max_iters)
        if problem.scalar:
            v = v[..., 0]
    from repro import faults as FI
    inj = FI.get()
    if inj is not None:
        v = inj.corrupt("solve_batched", v)

    n_iters = np.asarray(iters)
    final_delta = np.asarray(delta)
    total = int(it)
    kind = "stencil" if problem.stencil is not None else "flat"

    cen = np.asarray(v)
    b = cen.shape[0]
    lane_tol = lane_tolerances(problem, eps)
    healthy = np.isfinite(cen.reshape(b, -1)).all(axis=1)
    converged = np.asarray(final_delta < lane_tol) \
        & np.isfinite(final_delta)

    # Per-lane salvage: poisoned lanes always re-solve on the reference
    # loop (finite math beats a NaN result); unconverged lanes re-solve
    # only when the primary impl wasn't already the reference step
    # (identical math would just exhaust max_iters again).
    salvaged = np.zeros(b, dtype=bool)
    bad = ~healthy
    if impl != "reference":
        bad = bad | ~converged
    if salvage and bad.any():
        idx = np.nonzero(bad)[0]
        if problem.stencil is not None:
            v2, d2, i2, it2 = _stencil_batched_loop(
                problem.features[idx], c, m, problem.stencil.alpha,
                problem.stencil.neighbors, eps, max_iters)
        else:
            feats, w = problem.rows()
            v2, d2, i2, it2 = _flat_batched_loop(
                feats[idx], w[idx], c, m, eps, max_iters)
            if problem.scalar:
                v2 = v2[..., 0]
        cen = np.array(cen, copy=True)
        cen[idx] = np.asarray(v2)
        n_iters = np.array(n_iters, copy=True)
        n_iters[idx] = np.asarray(i2)
        final_delta = np.array(final_delta, copy=True)
        final_delta[idx] = np.asarray(d2)
        total = max(total, int(it2))
        healthy = np.isfinite(cen.reshape(b, -1)).all(axis=1)
        converged = np.asarray(final_delta < lane_tol) \
            & np.isfinite(final_delta)
        salvaged[idx] = True
        v = jnp.asarray(cen)
        from repro import obs
        obs.default_registry().counter(
            "solver.salvaged_lanes", kind=kind).inc(len(idx))

    _record_telemetry(kind, impl, total,
                      float(np.nanmax(final_delta)), lane_iters=n_iters)
    return BatchedFCMResult(centers=v, n_iters=n_iters,
                            final_delta=final_delta,
                            total_iters=total,
                            converged=converged, healthy=healthy,
                            salvaged=salvaged)


# ---------------------------------------------------------------------------
# Host-loop backends: the paper-faithful staged pipeline + sequential CPU
# ---------------------------------------------------------------------------

def solve_staged(problem: FCMProblem, *, eps: float = 5e-3,
                 max_iters: int = 300, seed: int = 0, u0=None,
                 keep_membership: bool = False,
                 use_pallas: bool = False) -> F.FCMResult:
    """The paper's pipeline: staged 'kernels' with the membership array
    materialized between stages and the convergence test
    ``|u' - u|_inf < eps`` on the HOST each iteration (the paper copies
    the membership back), random membership init. What
    ``solve(..., backend="staged")`` and the deprecated
    :func:`repro.core.fcm.fit_baseline` run; ``use_pallas=True`` routes
    the per-stage math through the Pallas kernels."""
    if problem.stencil is not None or problem.weights is not None:
        raise ValueError("backend='staged' reproduces the paper's "
                         "unweighted pixel pipeline only")
    x = problem.features
    n = x.shape[0]
    c, m = problem.c, problem.m
    key = jax.random.PRNGKey(seed)
    u = (F.random_membership(key, c, n) if u0 is None
         else jnp.asarray(u0, jnp.float32))
    if use_pallas:
        from repro.kernels import ops as kops

    n_iters = 0
    delta = jnp.inf
    v = None
    for it in range(max_iters):
        if use_pallas and x.ndim == 1:
            num, den = kops.center_partials(x, u, m)
            v = F._stage_combine(num, den)
            v = v[:, 0]
            u_new = kops.membership(x, v, m)
        else:
            num_terms, den_terms = F._stage_terms(x, u, m)
            num = F._stage_reduce_num(num_terms)
            den = F._stage_reduce_den(den_terms)
            v = F._stage_combine(num, den)
            v = v[:, 0] if x.ndim == 1 else v
            u_new = F._stage_membership(x, v, m)
        # Host round-trip, as in the paper's block diagram.
        delta = float(jnp.max(jnp.abs(u_new - u)))
        u = u_new
        n_iters = it + 1
        if delta < eps:
            break
    if v is None:
        # max_iters=0: centers from the initial membership, so the result
        # is still well-defined.
        v = F.update_centers(x, u, m)
    return F.FCMResult(centers=v, labels=F.defuzzify(u), n_iters=n_iters,
                       final_delta=delta,
                       membership=u if keep_membership else None,
                       converged=bool(delta < eps),
                       healthy=bool(np.isfinite(np.asarray(v)).all()))


def _solve_sequential(problem: FCMProblem, eps: float, max_iters: int,
                      seed: int, u0) -> F.FCMResult:
    """The paper's CPU comparison floor: single-core numpy, same
    algorithm/init as the literal C-port (see core/sequential.py)."""
    from . import sequential as S
    if problem.stencil is not None or problem.weights is not None \
            or not problem.scalar:
        raise ValueError("backend='sequential' is the scalar unweighted "
                         "CPU baseline only")
    v, labels, it = S.fcm_sequential_numpy(
        np.asarray(problem.features), c=problem.c, m=problem.m, eps=eps,
        max_iters=max_iters, seed=seed, u0=u0)
    # The comparator reports no residual (final_delta=NaN), so converged
    # is inferred from the iteration budget.
    return F.FCMResult(centers=jnp.asarray(v, jnp.float32),
                       labels=jnp.asarray(labels),
                       n_iters=int(it), final_delta=float("nan"),
                       converged=bool(int(it) < max_iters),
                       healthy=bool(np.isfinite(np.asarray(v)).all()))
