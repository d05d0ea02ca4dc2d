"""Request-batching segmentation engine over the unified solver core.

The LM :class:`~repro.serving.engine.ServeEngine` amortizes device
launches across a token batch; this engine does the same across *images*.
Every serving method is a declarative :class:`RouteSpec` in a route
registry — an ingest transform (validate / compress), a bucket key
(requests sharing one may share one device launch), a
:class:`RouteProgram` factory, and a cache policy. ``flush`` is
route-agnostic: group by bucket key, pad to a fixed batch size, run ONE
route-program launch per bucket. Adding an FCM variant to serving =
registering a RouteSpec and its program, not hand-routing a new queue.

*All four* methods batch across concurrent requests — including
``spatial`` (same-shape FCM_S grids stack into one per-lane-masked
stencil loop) and ``superpixel`` ((K, D) payload groups).
Two batching tricks keep XLA recompilation off the steady-state path:

* **Bucketing** — queued requests are padded up to the nearest size in
  ``batch_sizes`` (padding lanes are dropped on output), so only
  ``len(batch_sizes)`` jit signatures compile per payload shape (the
  pixel-exact route programs additionally key on payload size; both
  program caches are LRU-bounded so heterogeneous long-tail traffic
  recycles executables rather than accreting them).
* **Histogram-keyed LRU cache** — identical intensity histograms hit an
  exact-key lookup; near-identical ones (adjacent slices of a volume,
  repeat studies with fresh noise — L1 distance between normalized
  histograms below ``cache_tol``) hit a nearest-match scan. Either way
  the fit is skipped; only the cheap per-pixel defuzzification LUT
  gather runs. Only the histogram route is cacheable: spatial requests
  depend on pixel positions and vector features have no 256-bin key.

**Device-resident route programs** (the serving face of the paper's
"never leave the device" lesson): every route registers a
:class:`RouteProgram` — one *jitted* ingest->solve->defuzzify pipeline
per (route, bucket, payload-shape), cached and reused across flushes —
so a drained bucket is ONE device dispatch. On TPU the program's stages
are the Pallas binning / VMEM-resident whole-solve / fused defuzzify
kernels; off-TPU the binning runs as host numpy (XLA CPU has no fast
scatter) and the solve as the vmapped reference loop, still fused into
one dispatch. The same factory builds each route's *reference* program
(the registry's ``reference`` impls, same gather and scatter), which
serves chunks the circuit breaker degrades and lanes the salvage
re-solves. Re-registering a route bumps its generation and evicts its
compiled programs, so a replaced spec can never serve a stale pipeline.

Results are hard labels per request (same spatial shape as the input
image) plus the fitted centers; :meth:`FCMServeEngine.stats` exposes
queue / throughput / per-route request, batch and cache-hit counters,
plus a per-route ingest/solve/materialize stage-seconds breakdown.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import (Any, Callable, Dict, Hashable, List, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as _P

from repro import faults as FI
from repro import obs
from repro.core import distributed as DD
from repro.core import fcm as F
from repro.core import solver as SV
from repro.core import spatial as SP
from repro.kernels import fcm_resident as KR
from repro.kernels import ops as kops
from repro.superpixel import pipeline as SX

from .admission import (DeadlineExceeded, EngineShutdown, InvalidInput,
                        Overloaded, SegmentationFuture, SolveFailed)


@dataclasses.dataclass
class SegmentationResult:
    """Per-request output."""
    request_id: int
    #: Same spatial shape as the submitted image. The spatial route's
    #: are uint8 for c <= 256 (int32 above): a quarter of the bytes the
    #: chip sends back; the other routes' are int32.
    labels: np.ndarray
    centers: np.ndarray           # (c,) scalar or (c, D) vector features
    n_iters: int                  # 0 for cache hits
    cache_hit: bool
    method: str = "histogram"
    #: False when this request's lane exhausted its iteration budget
    #: without meeting the solver tolerance (the result is still the
    #: best available centers — degraded, not wrong-typed).
    converged: bool = True


def _validate_payload(img: np.ndarray) -> None:
    """Submit-time input guard: empty and non-finite float payloads are
    rejected with a typed :class:`InvalidInput` *before* they consume a
    request id or poison a shared batch lane. Integer payloads skip the
    finite scan (they cannot carry NaN/Inf) so the uint8 hot path pays
    nothing."""
    if img.size == 0:
        raise InvalidInput(f"empty image payload (shape {img.shape})")
    if img.dtype.kind == "f" and not np.isfinite(img).all():
        raise InvalidInput("image payload contains NaN/Inf pixels")


# ---------------------------------------------------------------------------
# Pending payloads (what each route's ingest produces)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Pending:
    """A histogram-route request. Ingest keeps only the clipped flat
    pixels: binning is deferred to the device program (Pallas on TPU) —
    ``hist``/``key`` are filled lazily and only when the LRU cache or
    the mixed-size fallback program actually needs them."""
    request_id: int
    shape: Tuple[int, ...]
    flat: np.ndarray              # flat bin indices: a zero-copy uint8
                                  # view for 8-bit payloads, clipped
                                  # int32 otherwise
    hist: Optional[np.ndarray] = None   # (n_bins,) float32, lazy
    key: Optional[bytes] = None         # cache/dedup key, lazy


@dataclasses.dataclass
class _PendingSpatial:
    """A spatial request carries the full pixel payload: FCM_S needs the
    pixel grid, so it can neither histogram-compress nor share the
    histogram cache. Same-shape grids still batch into one solve."""
    request_id: int
    pixels: np.ndarray            # original 2-D/3-D image, unreduced


@dataclasses.dataclass
class _PendingPixels:
    """A pixel request: uncompressed per-image fused FCM — the reference
    route every compression is measured against. (H, W, D) payloads
    cluster in D-dim feature space; same-shape payloads batch."""
    request_id: int
    pixels: np.ndarray


@dataclasses.dataclass
class _PendingSuperpixel:
    """A superpixel request after ingest-time SLIC compression: like the
    histogram route it carries only the reduced payload to the fit, but
    like the spatial route it bypasses the 1-D histogram LRU (vector
    features have no 256-bin key). ``features.shape`` buckets the batch."""
    request_id: int
    features: np.ndarray          # (K, D) superpixel mean features
    weights: np.ndarray           # (K,) pixel counts
    label_map: np.ndarray         # (H, W) int32 pixel -> superpixel
    slic_iters: int


# ---------------------------------------------------------------------------
# Route registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RouteSpec:
    """One serving method, declaratively.

    ``ingest(engine, img, rid)`` validates and reduces the payload (it
    must raise before consuming a request id on bad input);
    ``bucket_key(engine, payload)`` decides which payloads may share one
    batched launch; ``program_key(engine, chunk)`` names the
    compiled-program shape a drained chunk shares, and
    ``make_program(engine, key, bucket, reference=False)`` builds the
    :class:`RouteProgram` that turns the chunk (padded to ``bucket``)
    into answers, compiled once per (route generation, bucket, key) and
    cached on the engine; ``reference=True`` builds the same program on
    the registry's reference impls. ``cacheable`` routes carry a
    ``.key``/``.hist`` payload, go through the histogram LRU +
    intra-flush dedup, and answer those hits from fitted centers with
    ``materialize(engine, payload, centers, n_iters, cache_hit)``.
    """
    name: str
    ingest: Callable[["FCMServeEngine", np.ndarray, int], Any]
    bucket_key: Callable[["FCMServeEngine", Any], Hashable]
    program_key: Callable[["FCMServeEngine", List[Any]], Hashable]
    make_program: Callable[..., "RouteProgram"]
    materialize: Optional[
        Callable[["FCMServeEngine", Any, np.ndarray, int, bool],
                 SegmentationResult]] = None
    cacheable: bool = False
    stats_prefix: str = ""        # "" keeps the legacy histogram names

    def stat(self, name: str) -> str:
        if not self.stats_prefix:   # the histogram route predates routes
            return {"seconds": "fit_seconds", "iters": "fit_iters",
                    "batches": "batches", "images": "batched_images",
                    "padded": "padded_lanes",
                    "ingest": "ingest_seconds",
                    "compress": "compress_seconds",
                    "materialize": "materialize_seconds"}[name]
        legacy = {"seconds": "seconds", "iters": "iters",
                  "batches": "batches", "images": "batched_images",
                  "padded": "padded_lanes", "ingest": "ingest_seconds",
                  "compress": "compress_seconds",
                  "materialize": "materialize_seconds"}[name]
        return f"{self.stats_prefix}_{legacy}"


@dataclasses.dataclass(frozen=True)
class RouteProgram:
    """One compiled single-dispatch serving pipeline.

    ``gather(engine, chunk, bucket)`` finishes ingest on the host
    (stack + pad payloads into fixed-shape device inputs);
    ``launch(*inputs)`` is ONE jitted device dispatch covering
    ingest-binning, the batched solve and defuzzification;
    ``scatter(engine, chunk, outputs)`` unpacks the device outputs into
    per-request results and returns ``(results, centers (B, ...),
    n_iters (B,), total_iters, final_delta (B,))``, which flush-side
    stats, convergence telemetry and the LRU cache read alike for every
    route.
    ``impls`` names the registry kernels the launch resolved
    (``"kind/impl"``), reported by ``stats()["route_impls"]``.
    """
    gather: Callable[["FCMServeEngine", List[Any], int], Tuple]
    launch: Callable[..., Tuple]
    scatter: Callable[["FCMServeEngine", List[Any], Tuple],
                      Tuple[List[SegmentationResult], np.ndarray,
                            np.ndarray, int, np.ndarray]]
    impls: Tuple[str, ...] = ()


#: Module-level cache of *compiled* launch functions, keyed on the full
#: static math signature (route flavor, platform, bucket, shapes and
#: hyper-parameters). Engines hold their own RouteProgram cache for
#: generation-based eviction, but the jitted launch is shared here so a
#: fresh engine (cold LRU, same traffic shape) pays zero recompilation.
#: LRU-bounded: pixel-exact program flavors key on payload size, so
#: long-tail heterogeneous traffic must recycle executables instead of
#: retaining one per size ever seen for the process lifetime.
_LAUNCH_CACHE: "collections.OrderedDict[Hashable, Callable]" = \
    collections.OrderedDict()
_LAUNCH_CACHE_SIZE = 64


def _cached_launch(key: Hashable, build: Callable[[], Callable]) -> Callable:
    fn = _LAUNCH_CACHE.get(key)
    if fn is None:
        fn = build()
        _LAUNCH_CACHE[key] = fn
        while len(_LAUNCH_CACHE) > _LAUNCH_CACHE_SIZE:
            _LAUNCH_CACHE.popitem(last=False)
    else:
        _LAUNCH_CACHE.move_to_end(key)
    return fn


# ---------------------------------------------------------------------------
# Mesh dispatch: batch-axis-sharded launch programs
# ---------------------------------------------------------------------------

def _mesh_signature(mesh) -> Hashable:
    """A hashable identity for the mesh a launch was compiled against
    (device set + topology), so the module-level launch cache can never
    hand a program compiled for one mesh to an engine on another."""
    if mesh is None:
        return ("nomesh",)
    return ("mesh", tuple(mesh.axis_names), mesh.devices.shape,
            tuple(d.id for d in mesh.devices.flat))


def _shard_launch(mesh, launch_fn: Callable, n_in: int) -> Callable:
    """Wrap a RouteProgram launch body so its batch (leading) axis is
    sharded over every mesh axis. Lanes are independent images, so the
    body runs collective-free; only the scalar ``total`` (the shared
    trip count of each shard's masked loop) needs a pmax so every
    device reports the global batch's value. The wrapped function keeps
    the launch contract — ``(v, delta, iters, total, labels/lut)`` —
    and, on a one-device mesh, the identical math of the unsharded
    path (sharding a batch over one device is a no-op partition).
    """
    axes = DD.mesh_axes(mesh)
    bspec = _P(axes)

    def body(*inputs):
        v, delta, iters, total, tail = launch_fn(*inputs)
        return v, delta, iters, jax.lax.pmax(total, axes), tail

    return DD.shard_map(body, mesh=mesh,
                        in_specs=(bspec,) * n_in,
                        out_specs=(bspec, bspec, bspec, _P(), bspec))


def _jit_launch(eng: "FCMServeEngine", bucket: int, cache_key: Hashable,
                launch_fn: Callable, n_in: int,
                donate: Tuple[int, ...] = ()) -> Callable:
    """Compile (or fetch) the launch for this engine's mesh: sharded
    over the batch axis when the engine has a multi-device mesh that
    divides the bucket, the plain single-device jit otherwise. The mesh
    signature joins the cache key so single-device and per-mesh
    programs never collide."""
    mesh = eng._mesh_for_bucket(bucket)
    full_key = cache_key + (_mesh_signature(mesh),)
    if mesh is None:
        return _cached_launch(
            full_key, lambda: jax.jit(launch_fn, donate_argnums=donate))
    # No donation under shard_map: donated sharded buffers trip XLA
    # aliasing restrictions on some backends for zero win on this path.
    return _cached_launch(
        full_key, lambda: jax.jit(_shard_launch(mesh, launch_fn, n_in)))


def _impl(kind: str, reference: bool, **shape) -> str:
    """The registry impl a route program launches for ``kind``: the
    platform's pick for this shape, or ``reference`` when building the
    reference program that degraded and salvaged chunks run."""
    if reference:
        return "reference"
    return kops.select_step(kind, platform=jax.default_backend(),
                            **shape).name


ROUTES: "collections.OrderedDict[str, RouteSpec]" = collections.OrderedDict()

#: The stages of ``route.stage_seconds{route,stage}``: ``ingest``
#: (submit-time validation and reduction), ``gather`` (flush-time
#: stacking of a batch by its program's gather, fast or reference),
#: ``solve`` (the fenced launch) and inside it ``h2d`` (the inputs put on
#: the device), ``materialize`` (unpacking results) and inside it ``d2h``
#: (the outputs fetched to the host), ``compress`` (superpixel ingest).
STAGES = ("ingest", "gather", "solve", "h2d", "materialize", "d2h",
          "compress")

#: Route generations: bumped on every (re-)registration so engine-held
#: compiled programs for a replaced spec are evicted, never served stale.
_ROUTE_GEN: Dict[str, int] = collections.defaultdict(int)


def register_route(spec: RouteSpec) -> RouteSpec:
    """Add (or replace) a serving route; see the specs below for the
    shape. New FCM variants serve by registering here — ``flush`` and
    the stats plumbing need no changes. Replacing a spec invalidates
    any compiled route programs built from the old one."""
    ROUTES[spec.name] = spec
    _ROUTE_GEN[spec.name] += 1
    global METHODS
    METHODS = tuple(ROUTES)
    return spec


# -- histogram route --------------------------------------------------------

def _ingest_histogram(eng: "FCMServeEngine", img: np.ndarray,
                      rid: int) -> _Pending:
    # No binning here: the device program bins on-chip (Pallas kernel on
    # TPU); the histogram only materializes lazily for cache keys or the
    # mixed-size fallback program (see _ensure_hist). uint8 payloads
    # (the 8-bit serving case) cannot exceed the bin range, so ingest is
    # a zero-copy flat view — the request pipeline stays uint8 until the
    # device LUT gather.
    if img.dtype == np.uint8 and eng.n_bins >= 256:
        # .copy(), not a view: the caller may reuse its buffer between
        # submit() and flush() (a 16 KB memcpy, vs the clip+widen pass
        # the non-uint8 path pays).
        flat = img.reshape(-1).copy()
    else:
        flat = np.clip(img.reshape(-1), 0, eng.n_bins - 1).astype(np.int32)
    return _Pending(rid, img.shape, flat)


def _ensure_hist(eng: "FCMServeEngine", p: _Pending) -> _Pending:
    if p.hist is None:
        p.hist = np.bincount(p.flat, minlength=eng.n_bins
                             ).astype(np.float32)[:eng.n_bins]
        if p.key is None:       # dedup may have keyed on pixel bytes
            p.key = p.hist.tobytes()
    return p


def _label_lut(centers: np.ndarray, n_bins: int) -> np.ndarray:
    """n_bins-entry defuzzify LUT in plain numpy — identical f32
    arithmetic and tie-breaking to labels_from_centers, without a device
    dispatch per request (cache hits and duplicates ride this)."""
    vals = np.arange(n_bins, dtype=np.float32)
    c2 = np.asarray(centers, np.float32).reshape(-1, 1)
    return np.argmin((c2 - vals[None, :]) ** 2, axis=0).astype(np.int32)


def _materialize_histogram(eng, p, centers, n_iters, cache_hit):
    labels = _label_lut(centers, eng.n_bins)[p.flat].reshape(p.shape)
    return SegmentationResult(p.request_id, labels, np.asarray(centers),
                              n_iters, cache_hit)


def _histogram_program_key(eng, chunk):
    # Same-size payloads share the full pixels->binning->solve->labels
    # program (the defuzzify gather rides the dispatch: XLA's batched
    # gather beats a per-request numpy LUT loop even on CPU); mixed
    # sizes fall back to the histograms-only program + host LUT gather.
    sizes = {p.flat.size for p in chunk}
    return ("px", sizes.pop()) if len(sizes) == 1 else ("hist",)


def _make_histogram_program(eng, key, bucket,
                            reference=False) -> RouteProgram:
    cfg = eng.cfg
    c, m = cfg.n_clusters, float(cfg.m)
    eps, max_iters = float(cfg.eps), int(cfg.max_iters)
    nb = eng.n_bins
    platform = jax.default_backend()
    impl = _impl("flat", reference, n_feat=1, batched=True, n_rows=nb, c=c)
    vals = jnp.arange(nb, dtype=jnp.float32)

    def _solve_lut(hists):
        # feats derive from the *input* batch shape (not the bucket), so
        # the same body runs whole-bucket on one device or per-shard
        # under the mesh-sharded launch wrapper.
        feats = jnp.broadcast_to(vals[None, :, None], hists.shape + (1,))
        v, delta, iters, total = SV.flat_batched_solve(
            feats, hists, c, m, eps, max_iters, impl=impl)
        v2 = v[..., 0]
        lut = jax.vmap(lambda vv: F.labels_from_centers(vals, vv))(v2)
        return v2, delta, iters, total, lut

    def _gather_hists(eng_, chunk):
        # Padding lanes are all-ones histograms, dropped on output.
        hists = np.ones((bucket, nb), np.float32)
        for i, p in enumerate(chunk):
            hists[i] = _ensure_hist(eng_, p).hist
        return hists

    # The reference program keys apart: on the TPU its px launch takes
    # the host histograms where the fast one bins on-chip.
    cache_key = ("histogram", platform, bucket, key, nb, c, m, eps,
                 max_iters, impl) + (("reference",) if reference else ())

    if key[0] == "px":
        n = key[1]
        on_tpu = platform == "tpu" and not reference
        if on_tpu:
            def launch_fn(px):
                # Ingest binning on-chip: the Pallas one-pass kernel.
                # With the LRU enabled the cache lookup has already host-
                # binned these pixels for the key; the on-chip re-bin is
                # cheaper than widening the launch signature to ship the
                # host histograms in — the host bincount is the price of
                # a histogram-keyed cache, not of this program.
                hists = kops.histogram_counts(px, nb, interpret=False)
                v2, delta, iters, total, lut = _solve_lut(hists)
                return v2, delta, iters, total, \
                    jnp.take_along_axis(lut, px, axis=1)
            launch = _jit_launch(eng, bucket, cache_key, launch_fn, 1,
                                 donate=(0,))
        else:
            def launch_fn(px, hists):
                v2, delta, iters, total, lut = _solve_lut(hists)
                return v2, delta, iters, total, \
                    jnp.take_along_axis(lut, px, axis=1)
            launch = _jit_launch(eng, bucket, cache_key, launch_fn, 2)

        def gather(eng_, chunk, bucket_):
            # uint8 traffic stages uint8 (16 KB memcpy per lane); mixed
            # dtypes fall back to int32. Padding lanes replay lane 0.
            dtype = (np.uint8 if all(p.flat.dtype == np.uint8
                                     for p in chunk) else np.int32)
            px = np.empty((bucket_, n), dtype)
            for i, p in enumerate(chunk):
                px[i] = p.flat
            for i in range(len(chunk), bucket_):
                px[i] = px[0]
            if on_tpu:
                return (px,)
            return px, _gather_hists(eng_, chunk)

        def scatter(eng_, chunk, outs):
            v2, delta, iters, total, labels = outs
            centers = np.asarray(v2)
            iters_np = np.asarray(iters)
            labels_np = np.asarray(labels)
            res = [SegmentationResult(p.request_id,
                                      labels_np[i].reshape(p.shape),
                                      centers[i], int(iters_np[i]), False)
                   for i, p in enumerate(chunk)]
            return res, centers, iters_np, int(total), np.asarray(delta)

        return RouteProgram(gather, launch, scatter,
                            (("bin/pallas",) if on_tpu else ())
                            + (f"flat/{impl}",))

    # Mixed payload sizes: one solve dispatch on the stacked histograms,
    # per-request labels via the (cheap) host LUT gather.
    launch = _jit_launch(eng, bucket, cache_key,
                         lambda hists: _solve_lut(hists), 1)

    def gather(eng_, chunk, bucket_):
        return (_gather_hists(eng_, chunk),)

    def scatter(eng_, chunk, outs):
        v2, delta, iters, total, lut = outs
        centers = np.asarray(v2)
        iters_np = np.asarray(iters)
        lut_np = np.asarray(lut)
        res = [SegmentationResult(p.request_id,
                                  lut_np[i][p.flat].reshape(p.shape),
                                  centers[i], int(iters_np[i]), False)
               for i, p in enumerate(chunk)]
        return res, centers, iters_np, int(total), np.asarray(delta)

    return RouteProgram(gather, launch, scatter, (f"flat/{impl}",))


# -- pixel route ------------------------------------------------------------

def _ingest_pixel(eng, img, rid) -> _PendingPixels:
    # 3-D pixel payloads are channels-LAST feature stacks; a (D, H, W)
    # volume would silently cluster on W-dim rows, so anything that
    # doesn't look like trailing channels is rejected here (volumes
    # belong to histogram/spatial).
    if img.ndim not in (2, 3) or (img.ndim == 3 and img.shape[-1] > 16):
        raise ValueError(
            f"pixel requests need (H, W) or channels-last "
            f"(H, W, D<=16) input, got shape {img.shape}; "
            f"use method='histogram' or 'spatial' for volumes")
    return _PendingPixels(rid, img)


def _pixel_rows(img: np.ndarray) -> np.ndarray:
    imgf = img.astype(np.float32)
    return (imgf.reshape(-1, img.shape[-1]) if img.ndim == 3
            else imgf.reshape(-1))


def _pixel_program_key(eng, chunk):
    return ("px",) + chunk[0].pixels.shape  # bucket_key groups by shape


def _make_pixel_program(eng, key, bucket, reference=False) -> RouteProgram:
    shape = key[1:]
    scalar = len(shape) == 2
    d = 1 if scalar else shape[-1]
    n = int(np.prod(shape[:2]))
    cfg = eng.cfg
    c, m = cfg.n_clusters, float(cfg.m)
    eps, max_iters = float(cfg.eps), int(cfg.max_iters)
    platform = jax.default_backend()
    impl = _impl("flat", reference, n_feat=d, batched=True, n_rows=n, c=c)
    labels_impl = _impl("labels", reference, n_feat=d)

    def launch_fn(xs):
        w = jnp.ones(xs.shape[:2], jnp.float32)
        feats = xs[..., None] if scalar else xs
        v, delta, iters, total = SV.flat_batched_solve(
            feats, w, c, m, eps, max_iters, impl=impl)
        if scalar:
            v2 = v[..., 0]
            labels = kops.defuzzify_labels_batched(
                xs, v2, impl=labels_impl, interpret=False)
            return v2, delta, iters, total, labels
        labels = jax.vmap(F.labels_from_centers)(feats, v)
        return v, delta, iters, total, labels

    launch = _jit_launch(
        eng, bucket,
        ("pixel", platform, bucket, key, c, m, eps, max_iters, impl,
         labels_impl),
        launch_fn, 1, donate=(0,) if platform == "tpu" else ())

    def gather(eng_, chunk, bucket_):
        xs = np.empty((bucket_, n) if scalar else (bucket_, n, d),
                      np.float32)
        for i, q in enumerate(chunk):
            xs[i] = _pixel_rows(q.pixels)
        # Padding lanes replay the first image: each runs lane 0's whole
        # solve, and its answer is dropped.
        for i in range(len(chunk), bucket_):
            xs[i] = xs[0]
        return (xs,)

    def scatter(eng_, chunk, outs):
        v, delta, iters, total, labels = outs
        centers = np.asarray(v)
        iters_np = np.asarray(iters)
        labels_np = np.asarray(labels)
        res = [SegmentationResult(q.request_id,
                                  labels_np[i].reshape(shape[:2]),
                                  centers[i], int(iters_np[i]), False,
                                  method="pixel")
               for i, q in enumerate(chunk)]
        return res, centers, iters_np, int(total), np.asarray(delta)

    return RouteProgram(gather, launch, scatter,
                        (f"flat/{impl}", f"labels/{labels_impl}"))


# -- spatial route ----------------------------------------------------------

def _ingest_spatial(eng, img, rid) -> _PendingSpatial:
    if img.ndim not in (2, 3):
        raise ValueError(f"spatial requests need a (H, W) or (D, H, W) "
                         f"pixel grid, got shape {img.shape}")
    return _PendingSpatial(rid, img)


def _spatial_neighbors(eng, ndim: int) -> int:
    return eng.spatial_cfg.neighbors if ndim == 2 else 6


def _spatial_label_dtype(n_clusters: int) -> np.dtype:
    """The dtype of the spatial route's label maps: uint8 holds every
    label 0..c-1 exactly for c <= 256, and moves a quarter of int32's
    bytes from the chip; int32 above that."""
    return np.dtype(np.uint8 if n_clusters <= 256 else np.int32)


def _spatial_program_key(eng, chunk):
    # A chunk whose every lane is uint8 stages uint8 (a quarter of the
    # float32 bytes the H2D would move); any other chunk stages float32.
    # bucket_key groups by shape.
    dtype = np.dtype(np.uint8 if all(q.pixels.dtype == np.uint8
                                     for q in chunk) else np.float32)
    return ("sp", dtype) + chunk[0].pixels.shape


def _make_spatial_program(eng, key, bucket,
                          reference=False) -> "RouteProgram":
    """The fused spatial pipeline: stack -> batched FCM_S solve ->
    stencil-membership labeling, ONE jitted dispatch per flush. On TPU
    the solve stage is the VMEM-resident whole-solve stencil kernel
    (when the grid fits its bounds); off-TPU, and in the reference
    program, it is the vmapped reference stencil loop."""
    dtype, shape = key[1], key[2:]
    scfg = eng.spatial_cfg
    c, m = scfg.n_clusters, float(scfg.m)
    alpha = float(scfg.alpha)
    neighbors = _spatial_neighbors(eng, len(shape))
    eps, max_iters = float(scfg.eps), int(scfg.max_iters)
    label_dtype = _spatial_label_dtype(c)
    platform = jax.default_backend()
    impl = _impl("stencil", reference, batched=True,
                 n_rows=KR.stencil_pixels(shape), c=c)

    def launch_fn(imgs):
        # Widen on the chip: uint8 -> float32 is exact, so the solve sees
        # the very array float32 staging would have sent (flat uint8
        # lanes regain their grid shape; float32 lanes pass unchanged).
        imgs = imgs.reshape(imgs.shape[:1] + shape).astype(jnp.float32)
        v, delta, iters, total = SV.stencil_batched_solve(
            imgs, c, m, alpha, neighbors, eps, max_iters, impl=impl)
        u = jax.vmap(lambda im, vv: SP.spatial_membership(
            im, vv, m, alpha, neighbors))(imgs, v)
        # Narrow on the chip, and flat (bucket, pixels): the D2H then
        # moves uint8 rows, where a tiled (bucket, H, W) map pads and
        # fetches slower, most of all from several devices.
        labels = jnp.argmax(u, axis=1).astype(label_dtype)
        return v, delta, iters, total, labels.reshape(labels.shape[0], -1)

    launch = _jit_launch(
        eng, bucket,
        ("spatial", platform, bucket, key, c, m, alpha, neighbors, eps,
         max_iters, impl),
        launch_fn, 1)

    def gather(eng_, chunk, bucket_):
        imgs = np.empty((bucket_,) + shape, dtype)
        for i, q in enumerate(chunk):
            imgs[i] = q.pixels
        # Padding lanes replay the first image: each runs lane 0's whole
        # solve, and its answer is dropped.
        for i in range(len(chunk), bucket_):
            imgs[i] = imgs[0]
        # uint8 goes flat, (bucket, pixels), as the histogram route's
        # pixels do: a 2-D array the put need not relayout per slice.
        return (imgs.reshape(bucket_, -1) if dtype == np.uint8 else imgs,)

    def scatter(eng_, chunk, outs):
        v, delta, iters, total, labels = outs
        centers = np.asarray(v)
        iters_np = np.asarray(iters)
        labels_np = np.asarray(labels).reshape((-1,) + shape)
        res = [SegmentationResult(q.request_id, labels_np[i], centers[i],
                                  int(iters_np[i]), False,
                                  method="spatial")
               for i, q in enumerate(chunk)]
        return res, centers, iters_np, int(total), np.asarray(delta)

    return RouteProgram(gather, launch, scatter, (f"stencil/{impl}",))


# -- superpixel route -------------------------------------------------------

def _ingest_superpixel(eng, img, rid) -> _PendingSuperpixel:
    if img.ndim not in (2, 3):
        raise ValueError(f"superpixel requests need (H, W) or "
                         f"(H, W, D) input, got shape {img.shape}")
    # Per-route span + stage counter (not a global stat key): compress
    # is a stage of *this* route's ingest, and any future compressing
    # route gets its own `<prefix>_compress_seconds` for free.
    with eng.tracer.span("compress", ring=False, route="superpixel") as sp:
        comp = SX.compress(img.astype(np.float32), eng.superpixel_cfg)
    eng._stage_seconds("superpixel", "compress").inc(sp.wall_s)
    return _PendingSuperpixel(rid, np.asarray(comp.features),
                              np.asarray(comp.weights),
                              np.asarray(comp.label_map), comp.slic_iters)


def _superpixel_program_key(eng, chunk):
    return ("spx",) + chunk[0].features.shape  # bucket_key groups by shape


def _make_superpixel_program(eng, key, bucket,
                             reference=False) -> RouteProgram:
    """The superpixel fit: the weighted (K, D) solve of every lane and
    its per-superpixel labels in ONE dispatch (SLIC ran at ingest); the
    host maps each lane's labels through its pixel -> superpixel map.
    The superpixel config governs the fit (a caller-supplied one must
    win over self.cfg, not just steer the compression)."""
    k, d = key[1:]
    scfg = eng.superpixel_cfg
    c, m = scfg.n_clusters, float(scfg.m)
    eps, max_iters = float(scfg.eps), int(scfg.max_iters)
    platform = jax.default_backend()
    impl = _impl("flat", reference, n_feat=d, batched=True, n_rows=k, c=c)

    def launch_fn(feats, ws):
        v, delta, iters, total = SV.flat_batched_solve(
            feats, ws, c, m, eps, max_iters, impl=impl)
        return v, delta, iters, total, \
            jax.vmap(F.labels_from_centers)(feats, v)

    launch = _jit_launch(
        eng, bucket,
        ("superpixel", platform, bucket, key, c, m, eps, max_iters, impl),
        launch_fn, 2)

    def gather(eng_, chunk, bucket_):
        feats = np.empty((bucket_, k, d), np.float32)
        ws = np.empty((bucket_, k), np.float32)
        for i, q in enumerate(chunk):
            feats[i], ws[i] = q.features, q.weights
        # Padding lanes replay the first payload: each runs lane 0's
        # whole solve, and its answer is dropped.
        feats[len(chunk):], ws[len(chunk):] = feats[0], ws[0]
        return feats, ws

    def scatter(eng_, chunk, outs):
        v, delta, iters, total, sp_labels = outs
        centers = np.asarray(v)
        iters_np = np.asarray(iters)
        sp_np = np.asarray(sp_labels)
        res = [SegmentationResult(q.request_id, sp_np[i][q.label_map],
                                  centers[i], int(iters_np[i]), False,
                                  method="superpixel")
               for i, q in enumerate(chunk)]
        return res, centers, iters_np, int(total), np.asarray(delta)

    return RouteProgram(gather, launch, scatter, (f"flat/{impl}",))


register_route(RouteSpec(
    name="histogram", ingest=_ingest_histogram,
    bucket_key=lambda eng, p: ("hist",),
    program_key=_histogram_program_key,
    make_program=_make_histogram_program,
    materialize=_materialize_histogram, cacheable=True))
register_route(RouteSpec(
    name="pixel", ingest=_ingest_pixel,
    bucket_key=lambda eng, p: ("pixel",) + p.pixels.shape,
    program_key=_pixel_program_key,
    make_program=_make_pixel_program, stats_prefix="pixel"))
register_route(RouteSpec(
    name="spatial", ingest=_ingest_spatial,
    bucket_key=lambda eng, p: ("spatial",) + p.pixels.shape,
    program_key=_spatial_program_key,
    make_program=_make_spatial_program, stats_prefix="spatial"))
register_route(RouteSpec(
    name="superpixel", ingest=_ingest_superpixel,
    bucket_key=lambda eng, p: ("superpixel",) + p.features.shape,
    program_key=_superpixel_program_key,
    make_program=_make_superpixel_program, stats_prefix="superpixel"))

#: The serving routes, in registration order (the README routing table).
METHODS = tuple(ROUTES)


class FCMServeEngine:
    """Static-bucket batching engine for FCM segmentation requests.

    ``submit`` ingests an image through its route (any 2-D/3-D shape,
    8-bit-range values) and either answers from the cache or queues it.
    ``flush`` drains every route's queue through bucketed route-program
    launches. ``segment`` is the submit-all-then-flush convenience
    wrapper.

    **Async admission** (the continuous-batching front door):
    ``submit_async`` queues through the same per-route queues but hands
    back a :class:`~repro.serving.admission.SegmentationFuture`; a lazy
    background flusher thread forms batches — flushing when a bucket
    group reaches the target shape (``batch_sizes[-1]``) or when the
    oldest waiting async request exceeds ``max_wait_ms`` — and resolves
    futures as results materialize. ``drain()`` flushes synchronously
    (deterministic tests), ``shutdown()`` stops the flusher and either
    drains or fails the in-flight futures. The synchronous API is a
    degenerate case (no futures, caller-driven flush) and is untouched
    by the async machinery until the first ``submit_async``.

    **Mesh dispatch**: with a multi-device ``mesh``, every RouteProgram
    launch whose bucket divides by ``mesh.size`` is compiled with its
    batch axis sharded over the mesh (``core/distributed.shard_map``);
    program caches key on the mesh generation so ``set_mesh`` can never
    serve a stale single-device (or other-mesh) executable. A one-device
    mesh (or ``mesh=None``) runs the exact single-device path. Each
    sharded launch counts ``route.sharded_batches`` and its shard
    balance (``route.shard_iters_sum`` / ``route.shard_iters_max``), and
    the ``fcm.bucket`` annotation carries ``shards``.
    """

    def __init__(self, cfg: F.FCMConfig = F.FCMConfig(),
                 batch_sizes: Sequence[int] = (1, 8, 64),
                 n_bins: int = 256,
                 cache_size: int = 256,
                 cache_tol: float = 0.15,
                 spatial_cfg: Optional[SP.SpatialFCMConfig] = None,
                 superpixel_cfg: Optional[SX.SuperpixelFCMConfig] = None,
                 tracing: bool = True,
                 trace_ring: int = 64,
                 mesh=None,
                 max_wait_ms: float = 10.0,
                 faults: Optional[Any] = None,
                 retries: int = 2,
                 retry_backoff_s: float = 0.05,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 5.0,
                 max_queue_depth: Optional[int] = None):
        if not batch_sizes or any(b <= 0 for b in batch_sizes):
            raise ValueError(f"bad batch_sizes {batch_sizes!r}")
        self.cfg = cfg
        self.spatial_cfg = spatial_cfg or SP.SpatialFCMConfig(
            n_clusters=cfg.n_clusters, m=cfg.m, eps=cfg.eps,
            max_iters=cfg.max_iters)
        self.superpixel_cfg = superpixel_cfg or SX.SuperpixelFCMConfig(
            n_clusters=cfg.n_clusters, m=cfg.m, eps=cfg.eps,
            max_iters=cfg.max_iters)
        self.batch_sizes = tuple(sorted(set(int(b) for b in batch_sizes)))
        self.n_bins = n_bins
        self.cache_size = cache_size
        # Max L1 distance between normalized histograms for a near-match
        # cache hit; 0 restricts the cache to exact-histogram hits.
        self.cache_tol = cache_tol
        # key (exact histogram bytes) -> (centers, normalized histogram)
        self._cache: "collections.OrderedDict[bytes, Tuple[np.ndarray, np.ndarray]]" = \
            collections.OrderedDict()
        self._queues: Dict[str, List[Any]] = {name: [] for name in ROUTES}
        #: compiled RouteProgram cache keyed on (route, generation,
        #: bucket, payload-shape key); the generation key is what makes
        #: re-registered routes drop their stale programs.
        self._programs: Dict[Hashable, RouteProgram] = {}
        #: the same for reference programs (not in compiled_programs).
        self._ref_programs: Dict[Hashable, RouteProgram] = {}
        self._next_id = 0
        # All engine instrumentation lives on the obs layer: a private
        # MetricsRegistry (stats() renders the legacy flat keys from it)
        # plus a Tracer whose ring keeps the last ``trace_ring`` flush
        # traces. ``tracing=False`` keeps every stats counter (they are
        # the backward-compatible API) and the profiler annotations but
        # skips ring-buffer recording — the knob the tracing-overhead
        # benchmark toggles.
        self.metrics = obs.MetricsRegistry()
        self.tracer = obs.Tracer(max_traces=trace_ring, enabled=tracing)
        # -- fault tolerance ------------------------------------------------
        #: bounded retry on transient launch failures (exponential
        #: backoff: retry_backoff_s * 2^attempt between attempts).
        self.retries = int(retries)
        self.retry_backoff_s = float(retry_backoff_s)
        #: consecutive post-retry launch failures before a route's
        #: compiled program is circuit-broken to its reference program;
        #: after breaker_cooldown_s one half-open probe launch tests
        #: recovery.
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        #: queued-request ceiling: submits beyond it shed the lowest-
        #: urgency queued async request (or the incoming one) with a
        #: typed Overloaded error. None = unbounded (the default).
        self.max_queue_depth = (None if max_queue_depth is None
                                else int(max_queue_depth))
        if faults is None:
            self._faults: Optional[FI.FaultInjector] = None
        elif isinstance(faults, FI.FaultInjector):
            self._faults = faults
        else:
            self._faults = FI.FaultInjector(faults, registry=self.metrics)
        #: per-route breaker state {"state", "failures", "opened_t"};
        #: guarded by _lock.
        self._breakers: Dict[str, Dict[str, Any]] = {}
        #: hard (BaseException) flusher deaths observed; restarts are the
        #: "flusher.restarts" counter.
        self._flusher_kills = 0
        #: request id -> (submit perf_counter, route name); consumed when
        #: the request's result materializes, feeding the per-route
        #: submit->result latency histogram.
        self._submit_t: Dict[int, Tuple[float, str]] = {}
        # -- async admission state ----------------------------------------
        #: guards queues / futures / id allocation / shutdown flag; the
        #: condition wakes the flusher on submits and shutdown. RLock so
        #: submit_async can hold it across the whole enqueue+register
        #: critical section.
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        #: serializes flush *bodies* (flusher thread vs. drain/flush
        #: callers): queue swaps stay atomic under ``_lock``, the solve
        #: work runs outside it so submits never block on a device batch.
        self._flush_lock = threading.Lock()
        #: request id -> unresolved future (async requests only).
        self._futures: Dict[int, SegmentationFuture] = {}
        self.max_wait_ms = float(max_wait_ms)
        self._closed = False
        self._flusher: Optional[threading.Thread] = None
        # -- mesh dispatch state ------------------------------------------
        #: bumped by set_mesh; part of every program-cache key, so stale
        #: mesh programs are purged exactly like stale route generations.
        self._mesh_gen = 0
        self.mesh = None
        if mesh is not None:
            self.set_mesh(mesh)
        # Pre-register the schema for the routes known at construction
        # (zero-valued stats appear before any traffic; routes registered
        # later join lazily through the get-or-create registry).
        self.metrics.counter("requests")
        self.metrics.counter("cache_hits")
        self.metrics.gauge("queue.depth")
        self.metrics.counter("flusher.restarts")
        for route in ROUTES.values():
            self._route_counter("requests", route.name)
            self._route_counter("cache_hits", route.name)
            for k in ("batches", "images", "padded", "iters",
                      "deadline_expired", "retries", "shed", "salvaged",
                      "degraded", "breaker_trips", "invalid_input"):
                self._route_counter(k, route.name)
            self.metrics.gauge("route.breaker_state", route=route.name)
            for stage in STAGES:
                self._stage_seconds(route.name, stage)
            self._queue_wait(route.name)
            self._latency_hist(route.name)
            self._iters_hist(route.name)
            self._occupancy_hist(route.name)
            self.metrics.gauge("queue.depth", route=route.name)
        # Hot-path handles: submit runs per request, so the registry
        # lookups (each a lock + labelled-key probe) are hoisted out of
        # the admission path; depth gauges update incrementally and
        # _set_queue_gauges re-bases the total on queue swaps.
        self._qtotal = 0
        self._depth_gauge = self.metrics.gauge("queue.depth")
        self._depth_gauges = {
            name: self.metrics.gauge("queue.depth", route=name)
            for name in ROUTES}
        self._req_counter = self.metrics.counter("requests")
        self._req_counters = {
            name: self._route_counter("requests", name) for name in ROUTES}
        #: per-route count of queued async requests (guarded by _lock);
        #: lets submit_async wake the flusher only when the wake can
        #: change its schedule (first async request -> a new window
        #: deadline, or a full target shape -> flush due now).
        self._async_n: Dict[str, int] = {}

    # -- mesh ---------------------------------------------------------------

    def set_mesh(self, mesh) -> None:
        """Attach (or replace, or with ``None`` detach) the device mesh
        RouteProgram launches shard over. Bumps the mesh generation so
        every program compiled against the previous mesh is evicted on
        next use — a mesh swap can never serve a stale executable."""
        with self._lock:
            self.mesh = mesh
            self._mesh_gen += 1

    def _mesh_for_bucket(self, bucket: int):
        """The mesh a ``bucket``-lane launch shards over, or None for
        the single-device path (no mesh, a one-device mesh, or a bucket
        the mesh does not divide — ragged shards would need per-device
        padding for no win at these batch sizes)."""
        mesh = self.mesh
        if mesh is None or mesh.size <= 1 or bucket % mesh.size != 0:
            return None
        return mesh

    # -- metric accessors --------------------------------------------------

    def _route_counter(self, name: str, route_name: str) -> obs.Counter:
        return self.metrics.counter(f"route.{name}", route=route_name)

    def _stage_seconds(self, route_name: str, stage: str) -> obs.Counter:
        return self.metrics.counter("route.stage_seconds",
                                    route=route_name, stage=stage)

    def _queue_wait(self, route_name: str) -> obs.Counter:
        """Per-route seconds requests spent queued: submit -> the start
        of their batch's gather, summed over batched lanes."""
        return self.metrics.counter("route.queue_wait_seconds",
                                    route=route_name)

    def _latency_hist(self, route_name: str) -> obs.Histogram:
        """Per-route submit->result latency (seconds)."""
        return self.metrics.histogram("route.latency_seconds",
                                      route=route_name)

    def _iters_hist(self, route_name: str) -> obs.Histogram:
        """Per-route iterations-to-converge, one sample per real lane."""
        return self.metrics.histogram("route.lane_iters",
                                      edges=obs.ITER_EDGES,
                                      route=route_name)

    def _occupancy_hist(self, route_name: str) -> obs.Histogram:
        """Per-route batch occupancy: real lanes / bucket size, one
        sample per launched bucket (1.0 = no padding waste)."""
        return self.metrics.histogram("route.batch_occupancy",
                                      edges=obs.UNIT_EDGES,
                                      route=route_name)

    def _depth_gauge_for(self, method: str) -> obs.Gauge:
        g = self._depth_gauges.get(method)
        if g is None:
            g = self._depth_gauges.setdefault(
                method, self.metrics.gauge("queue.depth", route=method))
        return g

    def _set_queue_gauges(self) -> None:
        """Re-base the per-route + global queue-depth gauges from the
        actual queues (caller holds ``_lock``; used on queue swaps —
        per-submit updates are incremental in ``_enqueue``)."""
        total = 0
        for name, q in self._queues.items():
            self._depth_gauge_for(name).set(len(q))
            total += len(q)
        self._qtotal = total
        self._depth_gauge.set(total)

    def _finish(self, route: RouteSpec, results: Dict[int, Any],
                r: SegmentationResult) -> None:
        """Record one materialized result + its submit->result latency,
        and resolve the request's future if it was submitted async."""
        results[r.request_id] = r
        sub = self._submit_t.pop(r.request_id, None)
        if sub is not None:
            self._latency_hist(route.name).record(
                time.perf_counter() - sub[0])
        fut = self._futures.pop(r.request_id, None)
        if fut is not None:
            fut.try_set_result(r)

    def _fail_request(self, p: Any, err: BaseException) -> bool:
        """Resolve one request's bookkeeping with a typed error; returns
        True when an async future took it (sync callers have no future —
        their flush must surface the error itself)."""
        self._submit_t.pop(p.request_id, None)
        fut = self._futures.pop(p.request_id, None)
        if fut is not None:
            fut.try_set_exception(err)
            return True
        return False

    # -- ingest ------------------------------------------------------------

    def _ingest(self, method: str, img: np.ndarray):
        """Validate + reduce one payload through its route (outside the
        admission lock: superpixel ingest runs SLIC)."""
        route = ROUTES.get(method)
        if route is None:
            raise ValueError(f"unknown method {method!r}; registered "
                             f"routes: {METHODS}")
        img = np.asarray(img)
        # Ingest validates eagerly: a request failing inside flush()
        # would discard the whole drained batch's results. A raise here
        # consumes neither a request id nor a queue slot (the span
        # records status="error" and re-raises before any counter but
        # the invalid-input tally moves).
        try:
            with self.tracer.span("ingest", ring=False, route=method) as sp:
                if self._faults is not None:
                    self._faults.maybe_fail("ingest", route=method)
                _validate_payload(img)
                pending = route.ingest(self, img, self._next_id)
        except InvalidInput:
            self._route_counter("invalid_input", method).inc()
            raise
        self._stage_seconds(method, "ingest").inc(sp.wall_s)
        return pending

    def _enqueue(self, method: str, pending, t_submit: float) -> int:
        """Allocate the request id and queue the payload (caller holds
        ``_lock``)."""
        if self._closed:
            raise EngineShutdown("engine is shut down; no new submits")
        rid = self._next_id
        self._next_id += 1
        # The id passed to ingest was advisory (allocation races with
        # other submitters); the queued payload carries the real one.
        pending.request_id = rid
        self._req_counter.inc()
        rc = self._req_counters.get(method)
        if rc is None:
            rc = self._req_counters.setdefault(
                method, self._route_counter("requests", method))
        rc.inc()
        self._submit_t[rid] = (t_submit, method)
        q = self._queues.setdefault(method, [])
        q.append(pending)
        self._depth_gauge_for(method).set(len(q))
        self._qtotal += 1
        self._depth_gauge.set(self._qtotal)
        return rid

    def submit(self, img: np.ndarray, method: str = "histogram") -> int:
        """Queue one image on a registered route; returns its request id.
        Cache hits are still materialized at flush time (the defuzzify
        LUT needs the pixels). See ``METHODS`` / the README routing
        table for the built-in routes."""
        t_submit = time.perf_counter()
        pending = self._ingest(method, img)
        with self._lock:
            return self._enqueue(method, pending, t_submit)

    def submit_async(self, img: np.ndarray, method: str = "histogram",
                     deadline: Optional[float] = None) -> SegmentationFuture:
        """Queue one image and return a future for its result.

        ``deadline`` is relative seconds from now: a request still
        queued when its deadline passes resolves with
        :class:`~repro.serving.admission.DeadlineExceeded` instead of
        running (a non-positive deadline fails at submit, consuming no
        request id or queue slot). Batches form in the background —
        when a bucket group reaches the target shape
        (``batch_sizes[-1]``) or the oldest waiting async request
        exceeds ``max_wait_ms`` — or deterministically via ``drain()``.
        Raises :class:`~repro.serving.admission.EngineShutdown` after
        ``shutdown()``.
        """
        t_submit = time.perf_counter()
        if method not in ROUTES:
            raise ValueError(f"unknown method {method!r}; registered "
                             f"routes: {METHODS}")
        if self._closed:
            raise EngineShutdown("engine is shut down; no new submits")
        if deadline is not None and deadline <= 0:
            fut = SegmentationFuture(-1, method, deadline=t_submit)
            fut.submit_t = t_submit
            self._route_counter("deadline_expired", method).inc()
            fut.set_exception(DeadlineExceeded(
                f"deadline {deadline}s already expired at submit"))
            return fut
        try:
            pending = self._ingest(method, img)
        except (InvalidInput, FI.InjectedFault) as e:
            # Same semantics as an already-expired deadline: a failed
            # future, no request id, no queue slot. Injected ingest
            # faults take the same door — a payload that dies during
            # decode must fail only its own submit.
            fut = SegmentationFuture(-1, method)
            fut.submit_t = t_submit
            fut.set_exception(e)
            return fut
        abs_deadline = None if deadline is None else t_submit + deadline
        with self._lock:
            if (self.max_queue_depth is not None
                    and self._qtotal >= self.max_queue_depth
                    and not self._shed_for(
                        float("inf") if abs_deadline is None
                        else abs_deadline)):
                # Every queued request is at least as urgent as this
                # one: shed the incoming request instead.
                self._route_counter("shed", method).inc()
                fut = SegmentationFuture(-1, method, deadline=abs_deadline)
                fut.submit_t = t_submit
                fut.set_exception(Overloaded(
                    f"queue depth {self._qtotal} at max_queue_depth="
                    f"{self.max_queue_depth}; request shed"))
                return fut
            rid = self._enqueue(method, pending, t_submit)
            fut = SegmentationFuture(rid, method, deadline=abs_deadline)
            fut.submit_t = t_submit
            self._futures[rid] = fut
            self._ensure_flusher()
            # Wake the flusher only when this submit can change its
            # schedule: the route's first queued async request starts a
            # max_wait window; every target-shape-multiple of queued
            # requests may complete a full bucket group (mixed-shape
            # groups that straddle the multiple still flush at the
            # window — the wake is an early trigger, not the backstop).
            n_async = self._async_n.get(method, 0) + 1
            self._async_n[method] = n_async
            if (n_async == 1
                    or len(self._queues[method]) % self.batch_sizes[-1]
                    == 0):
                self._cond.notify_all()
        return fut

    def _shed_for(self, incoming_deadline: float) -> bool:
        """Overload shedding (caller holds ``_lock``): fail the single
        *least urgent* queued async request — the one with the farthest
        (or no) deadline — with :class:`Overloaded`, freeing its slot
        for a strictly more urgent incoming request. Returns False when
        nothing queued is less urgent (ties shed the incoming request:
        it is the newest) or only sync requests are queued (their
        callers hold no future to fail)."""
        worst: Optional[Tuple[Tuple[float, int], str, Any]] = None
        for name, q in self._queues.items():
            for p in q:
                fut = self._futures.get(p.request_id)
                if fut is None:
                    continue
                d = (fut.deadline if fut.deadline is not None
                     else float("inf"))
                key = (d, p.request_id)
                if worst is None or key > worst[0]:
                    worst = (key, name, p)
        if worst is None or worst[0][0] <= incoming_deadline:
            return False
        (_, rid), name, p = worst
        self._queues[name].remove(p)
        self._qtotal -= 1
        self._depth_gauge.set(self._qtotal)
        self._depth_gauge_for(name).set(len(self._queues[name]))
        if self._async_n.get(name):
            self._async_n[name] -= 1
        self._route_counter("shed", name).inc()
        self._submit_t.pop(rid, None)
        fut = self._futures.pop(rid, None)
        if fut is not None:
            fut.try_set_exception(Overloaded(
                f"request {rid} shed under overload (queue at "
                f"max_queue_depth={self.max_queue_depth})"))
        return True

    @staticmethod
    def _normalize(hist: np.ndarray) -> np.ndarray:
        return hist / max(float(hist.sum()), 1.0)

    # -- drain -------------------------------------------------------------

    def flush(self, raise_errors: bool = True) -> List[SegmentationResult]:
        """Run every queued request; returns results in submit order.
        Route-agnostic: cache/dedup for cacheable routes, then group by
        bucket key and run one batched solve per bucket. Each flush
        leaves one root trace (per-bucket child spans inside) in
        ``tracer``'s ring.

        Thread-safe: the queue swap is atomic under the admission lock
        and flush bodies are serialized, so the background flusher and
        explicit flush/drain callers can never process one request
        twice. A route whose batch raises fails that route's
        unresolved futures with the error; with ``raise_errors`` (the
        synchronous default) the first error then propagates, while the
        background flusher passes ``False`` so one poisoned route never
        kills the thread serving the others."""
        results: Dict[int, SegmentationResult] = {}
        first_err: Optional[BaseException] = None
        with self._flush_lock:
            with self._lock:
                drained = {name: self._queues[name] for name in self._queues}
                for name in drained:
                    self._queues[name] = []
                self._async_n = {}
                self._set_queue_gauges()
            n_queued = sum(len(v) for v in drained.values())
            with self.tracer.span("flush", queued=n_queued):
                for route in ROUTES.values():
                    pend = self._admit_order(route,
                                             drained.get(route.name) or [])
                    if not pend:
                        continue
                    try:
                        self._flush_route(route, pend, results)
                    except BaseException as e:  # noqa: BLE001
                        for p in pend:
                            if p.request_id in results:
                                continue
                            self._fail_request(p, e)
                        if first_err is None:
                            first_err = e
        if first_err is not None and raise_errors:
            raise first_err
        return [results[rid] for rid in sorted(results)]

    def _admit_order(self, route: RouteSpec, pend: List[Any]) -> List[Any]:
        """Deadline admission on a drained route queue: expire overdue
        async requests (their futures fail with ``DeadlineExceeded``
        without spending a solver lane) and order survivors
        most-urgent-first, so tight-deadline requests land in the
        earliest chunk of their bucket group. Sync requests carry no
        deadline and keep their submit order."""
        now = time.perf_counter()
        keep: List[Any] = []
        for p in pend:
            fut = self._futures.get(p.request_id)
            if (fut is not None and fut.deadline is not None
                    and now > fut.deadline):
                self._futures.pop(p.request_id, None)
                self._submit_t.pop(p.request_id, None)
                self._route_counter("deadline_expired", route.name).inc()
                fut.try_set_exception(DeadlineExceeded(
                    f"request {p.request_id} missed its deadline "
                    f"while queued"))
                continue
            keep.append(p)

        def urgency(p):
            fut = self._futures.get(p.request_id)
            d = (fut.deadline
                 if fut is not None and fut.deadline is not None
                 else float("inf"))
            return (d, p.request_id)

        keep.sort(key=urgency)
        return keep

    def _flush_route(self, route: RouteSpec, pend: List[Any],
                     results: Dict[int, SegmentationResult]) -> None:
        """One route's share of a flush: cache/dedup, bucket, solve."""
        dups: List[Any] = []
        fitted: Dict[bytes, np.ndarray] = {}
        if route.cacheable:
            pend, dups = self._answer_from_cache(route, pend, results)
        groups: "collections.OrderedDict[Hashable, List[Any]]" = \
            collections.OrderedDict()
        for p in pend:
            groups.setdefault(route.bucket_key(self, p), []).append(p)
        for group in groups.values():
            i = 0
            while i < len(group):
                chunk = group[i:i + self.batch_sizes[-1]]
                i += len(chunk)
                self._run_bucket(route, chunk,
                                 self._bucket_for(len(chunk)),
                                 results, fitted)
        # duplicates ride on their representative's centers (kept
        # locally: the LRU may be disabled, or evict mid-flush)
        for p in dups:
            self.metrics.counter("cache_hits").inc()
            self._route_counter("cache_hits", route.name).inc()
            self._finish(route, results, route.materialize(
                self, p, fitted[p.key], 0, True))

    def drain(self) -> List[SegmentationResult]:
        """Deterministically flush everything queued, resolving every
        pending future; returns the materialized results. A zero-request
        drain is a cheap no-op returning ``[]``. If the background
        flusher is mid-flush, ``drain`` waits for that batch (flush
        bodies serialize), so every request submitted before the call
        is resolved when it returns."""
        return self.flush()

    def segment(self, imgs: Sequence[np.ndarray],
                method: str = "histogram") -> List[SegmentationResult]:
        ids = [self.submit(im, method=method) for im in imgs]
        by_id = {r.request_id: r for r in self.flush()}
        return [by_id[i] for i in ids]

    # -- background flusher ------------------------------------------------

    def _ensure_flusher(self) -> None:
        """Start the batch-formation thread lazily (caller holds
        ``_lock``): engines serving only the synchronous API never pay
        for — or behave differently because of — a background thread.
        Called on *every* async submit, so a flusher that died hard
        (anything escaping the supervised loop, including an injected
        :class:`~repro.faults.FlusherKilled`) is replaced before the new
        request could ever hang on a dead thread."""
        if self._flusher is not None and not self._flusher.is_alive():
            # Replacing a dead thread (supervised restarts inside a live
            # loop count themselves).
            self.metrics.counter("flusher.restarts").inc()
            self._flusher = None
        if self._flusher is None:
            self._flusher = threading.Thread(
                target=self._flusher_loop, name="fcm-serve-flusher",
                daemon=True)
            self._flusher.start()

    def _flush_due(self) -> Optional[float]:
        """Batch-formation policy (caller holds ``_lock``): seconds
        until the next flush is due — ``0.0`` for *due now* (some bucket
        group reached the target shape, or the oldest waiting async
        request exceeded ``max_wait_ms``), ``None`` for *nothing async
        waiting* (sleep until a submit wakes us)."""
        now = time.perf_counter()
        oldest: Optional[float] = None
        target = self.batch_sizes[-1]
        for name, q in self._queues.items():
            route = ROUTES.get(name)
            if route is None or not q:
                continue
            group_sizes: Dict[Hashable, int] = {}
            async_here = False
            for p in q:
                k = route.bucket_key(self, p)
                group_sizes[k] = group_sizes.get(k, 0) + 1
                if p.request_id in self._futures:
                    async_here = True
                    t = self._submit_t.get(p.request_id)
                    if t is not None and (oldest is None or t[0] < oldest):
                        oldest = t[0]
            # Target-shape trigger: only once async traffic is involved
            # (pure sync queues belong to their caller's flush).
            if async_here and any(n >= target
                                  for n in group_sizes.values()):
                return 0.0
        if oldest is None:
            return None
        return max(0.0, oldest + self.max_wait_ms / 1000.0 - now)

    def _flusher_loop(self) -> None:
        # Supervised: the whole iteration body is wrapped, so a raise
        # anywhere — _flush_due bookkeeping on a malformed payload, the
        # flush machinery itself — restarts the loop in place (counted
        # in flusher.restarts) instead of silently killing the thread
        # with async clients parked on it forever. Only BaseException
        # (thread-kill) escapes; _ensure_flusher replaces the thread on
        # the next async submit.
        while True:
            try:
                if self._faults is not None:
                    self._faults.maybe_fail("flusher")
                with self._lock:
                    while True:
                        if self._closed:
                            return
                        wait = self._flush_due()
                        if wait is not None and wait <= 0.0:
                            break
                        self._cond.wait(timeout=wait)
                # Outside the lock: the flush body serializes on
                # _flush_lock and swaps queues atomically; per-route
                # errors have already been routed into the affected
                # futures (raise_errors=False).
                self.flush(raise_errors=False)
            except FI.FlusherKilled:
                # Hard thread death. If work is still pending, spawn a
                # replacement before dying — parked futures must never
                # hang on a corpse (submit_async also re-ensures, but a
                # lone in-flight request has no later submit to do it).
                with self._lock:
                    self._flusher_kills += 1
                    self._flusher = None
                    if not self._closed and (
                            self._qtotal > 0
                            or sum(self._async_n.values()) > 0):
                        self.metrics.counter("flusher.restarts").inc()
                        self._ensure_flusher()
                return
            except Exception:   # noqa: BLE001 — supervised restart
                self.metrics.counter("flusher.restarts").inc()
                continue

    def shutdown(self, drain: bool = True) -> None:
        """Stop the background flusher and close admission. With
        ``drain`` (default), everything still queued is flushed and
        every future resolves with its result; with ``drain=False``,
        queued requests are dropped and their futures fail with
        :class:`~repro.serving.admission.EngineShutdown`. Subsequent
        submits raise ``EngineShutdown``; ``shutdown`` is idempotent."""
        with self._lock:
            already = self._closed
            self._closed = True
            self._cond.notify_all()
            flusher = self._flusher
        if flusher is not None and flusher.is_alive():
            flusher.join()
        if already:
            return
        if drain:
            self.flush(raise_errors=False)
            return
        with self._lock:
            dropped: List[Any] = []
            for name in self._queues:
                dropped.extend(self._queues[name])
                self._queues[name] = []
            self._async_n = {}
            self._set_queue_gauges()
        err = EngineShutdown("engine shut down with the request queued")
        for p in dropped:
            self._fail_request(p, err)

    @property
    def closed(self) -> bool:
        return self._closed

    def _answer_from_cache(self, route: RouteSpec, pend: List[Any],
                           results: Dict[int, SegmentationResult]):
        """Cache lookups + intra-flush dedup (one fit per distinct key);
        returns (representatives to fit, duplicates). With the LRU
        disabled neither histograms nor dedup keys are ever computed:
        duplicate payloads simply occupy identical lanes of the batched
        solve (identical lanes converge identically, so results match)
        — hashing 64 KB of pixels per request to *maybe* merge lanes
        inside an already-padded bucket costs more than it saves."""
        misses: List[Any] = []
        if self.cache_size <= 0:
            return pend, []
        for p in pend:
            _ensure_hist(self, p)
            centers = self._cache_get(p.key, p.hist)
            if centers is not None:
                self.metrics.counter("cache_hits").inc()
                self._route_counter("cache_hits", route.name).inc()
                self._finish(route, results, route.materialize(
                    self, p, centers, 0, True))
            else:
                misses.append(p)
        uniq: Dict[bytes, Any] = {}
        dups: List[Any] = []
        for p in misses:
            if p.key in uniq:
                dups.append(p)
            else:
                uniq[p.key] = p
        return list(uniq.values()), dups

    def _bucket_for(self, n: int) -> int:
        for b in self.batch_sizes:
            if n <= b:
                return b
        return self.batch_sizes[-1]

    def _program_for(self, route: RouteSpec,
                     chunk: List[Any], bucket: int) -> RouteProgram:
        """The compiled single-dispatch program this chunk rides."""
        return self._cached_program(self._programs, route, chunk, bucket,
                                    reference=False)

    def _reference_program_for(self, route: RouteSpec, chunk: List[Any],
                               bucket: int) -> RouteProgram:
        """The chunk's reference program: the same gather, launch body
        and scatter on the registry's reference impls. Degraded chunks
        and salvaged lanes run it."""
        return self._cached_program(self._ref_programs, route, chunk,
                                    bucket, reference=True)

    def _cached_program(self, cache: Dict[Hashable, RouteProgram],
                        route: RouteSpec, chunk: List[Any], bucket: int,
                        reference: bool) -> RouteProgram:
        """Programs are cached per (route generation, mesh generation,
        bucket, shape key); stale generations — a re-registered route OR
        a swapped mesh — are purged here."""
        key = route.program_key(self, chunk)
        gen = _ROUTE_GEN[route.name]
        stale = [k for k in cache
                 if (k[0] == route.name and k[1] != gen)
                 or k[2] != self._mesh_gen]
        for k in stale:
            del cache[k]
        full_key = (route.name, gen, self._mesh_gen, bucket, key)
        prog = cache.get(full_key)
        if prog is None:
            prog = route.make_program(self, key, bucket, reference=reference)
            cache[full_key] = prog
            # Same bound rationale as _LAUNCH_CACHE: size-keyed program
            # flavors must not accumulate one entry per payload size.
            while len(cache) > _LAUNCH_CACHE_SIZE:
                del cache[next(iter(cache))]
        return prog

    # -- circuit breaker + retry (the graceful-degradation ladder) ---------

    _BREAKER_GAUGE = {"closed": 0.0, "half_open": 0.5, "open": 1.0}

    def _breaker(self, route_name: str) -> Dict[str, Any]:
        b = self._breakers.get(route_name)
        if b is None:
            b = {"state": "closed", "failures": 0, "opened_t": 0.0}
            self._breakers[route_name] = b
        return b

    def _set_breaker(self, route_name: str, b: Dict[str, Any],
                     state: str) -> None:
        b["state"] = state
        self.metrics.gauge("route.breaker_state", route=route_name).set(
            self._BREAKER_GAUGE[state])

    def _breaker_allows(self, route_name: str) -> bool:
        """May this chunk ride the route's compiled program? ``closed``
        -> yes; ``open`` -> no until ``breaker_cooldown_s`` elapses,
        then exactly one half-open probe launch tests recovery;
        ``half_open`` -> no (a probe is already in flight)."""
        with self._lock:
            b = self._breaker(route_name)
            if b["state"] == "closed":
                return True
            if b["state"] == "open" and (
                    time.perf_counter() - b["opened_t"]
                    >= self.breaker_cooldown_s):
                self._set_breaker(route_name, b, "half_open")
                return True
            return False

    def _breaker_success(self, route_name: str) -> None:
        with self._lock:
            b = self._breaker(route_name)
            if b["state"] != "closed" or b["failures"]:
                b["failures"] = 0
                self._set_breaker(route_name, b, "closed")

    def _breaker_failure(self, route_name: str) -> None:
        """One post-retry launch failure: count toward the trip
        threshold (closed) or fail the recovery probe straight back to
        open with a fresh cooldown (half_open)."""
        with self._lock:
            b = self._breaker(route_name)
            if b["state"] == "half_open":
                b["opened_t"] = time.perf_counter()
                self._route_counter("breaker_trips", route_name).inc()
                self._set_breaker(route_name, b, "open")
                return
            b["failures"] += 1
            if (b["state"] == "closed"
                    and b["failures"] >= self.breaker_threshold):
                b["opened_t"] = time.perf_counter()
                self._route_counter("breaker_trips", route_name).inc()
                self._set_breaker(route_name, b, "open")

    def _launch_attempts(self, route: RouteSpec, prog: RouteProgram,
                         inputs: Tuple, bucket: int,
                         reference: bool = False) -> Tuple[Tuple, float]:
        """One program launch under the bounded-retry policy: transient
        failures (injected faults, launch-time runtime errors) retry up
        to ``retries`` times with exponential backoff; programming
        errors (ValueError/TypeError) and the final failure propagate —
        the caller advances the breaker and degrades the chunk. A
        ``reference`` program, the last resort, makes one attempt and
        passes no ``launch`` fault site. Each
        attempt first puts the host ``inputs`` on the device with the
        launch's sharding (an ``h2d`` span, fenced: the batch axis split
        over the mesh ``_jit_launch`` shards this bucket over, so no
        reshard runs inside the launch, and ``route.h2d_bytes`` counts
        the bytes each put moved); returns the launch's outputs and the
        seconds those puts took."""
        mesh = self._mesh_for_bucket(bucket)
        sharding = (None if mesh is None
                    else NamedSharding(mesh, _P(DD.mesh_axes(mesh))))
        attempt = 0
        h2d_s = 0.0
        while True:
            try:
                if self._faults is not None and not reference:
                    self._faults.maybe_fail("launch", route=route.name)
                with self.tracer.span("h2d", route=route.name) as sp:
                    dev = sp.fence(jax.device_put(inputs, sharding))
                h2d_s += sp.wall_s
                self._route_counter("h2d_bytes", route.name).inc(
                    sum(x.nbytes for x in inputs))
                return prog.launch(*dev), h2d_s
            except (ValueError, TypeError):
                raise
            except Exception:
                if reference or attempt >= self.retries:
                    raise
                self._route_counter("retries", route.name).inc()
                time.sleep(self.retry_backoff_s * (2 ** attempt))
                attempt += 1

    def _count_shards(self, route_name: str, iters: np.ndarray,
                      shards: int) -> None:
        """Shard balance of one sharded launch, from the per-lane
        iterations it returned: each shard runs its contiguous
        ``bucket / shards`` lanes (padding lanes included) one after
        another, so the shard with the most lane iterations sets the
        launch's time. ``route.shard_iters_sum`` adds every shard's lane
        iterations and ``route.shard_iters_max`` ``shards`` times the
        largest shard's: their ratio is the balance (1.0 is even)."""
        per = np.asarray(iters, np.int64).reshape(shards, -1).sum(axis=1)
        self._route_counter("sharded_batches", route_name).inc()
        self._route_counter("shard_iters_sum", route_name).inc(
            int(per.sum()))
        self._route_counter("shard_iters_max", route_name).inc(
            shards * int(per.max()))

    def _route_cfg(self, route: RouteSpec):
        """The config whose eps/max_iters govern this route's fits."""
        return {"spatial": self.spatial_cfg,
                "superpixel": self.superpixel_cfg}.get(route.name, self.cfg)

    def _gather_launch(self, route: RouteSpec, prog: RouteProgram,
                       chunk: List[Any], bucket: int,
                       reference: bool = False) -> Tuple[Tuple, Tuple, Tuple]:
        """Stack ``chunk`` with the program's gather and launch it, in
        the ``gather`` and fenced ``launch`` spans; returns the device
        outputs, the host inputs and the (gather, launch, h2d) seconds."""
        with self.tracer.span("gather", route=route.name) as sp_g:
            inputs = prog.gather(self, chunk, bucket)
        with self.tracer.span("launch", route=route.name) as sp_s:
            outs, h2d_s = self._launch_attempts(route, prog, inputs, bucket,
                                                reference)
            outs = sp_s.fence(outs)
        return outs, inputs, (sp_g.wall_s, sp_s.wall_s, h2d_s)

    def _scatter(self, route: RouteSpec, prog: RouteProgram,
                 chunk: List[Any], outs: Tuple) -> Tuple[Tuple, Tuple, Tuple]:
        """Fetch a launch's outputs (the ``d2h`` span) and unpack them with
        the program's scatter (the ``scatter`` span); returns the scatter's
        tuple, the fetched outputs and the (scatter, d2h) seconds."""
        with self.tracer.span("scatter", route=route.name) as sp_m:
            with self.tracer.span("d2h", route=route.name) as sp_d:
                outs = jax.device_get(outs)
            scattered = prog.scatter(self, chunk, outs)
        return scattered, outs, (sp_m.wall_s, sp_d.wall_s)

    def _salvage_requests(self, route: RouteSpec, bad: List[Any],
                          results: Dict[int, SegmentationResult],
                          fitted: Dict[bytes, np.ndarray]) -> None:
        """Re-solve poisoned requests on the route's reference program
        in their own mini-bucket and finish them from the clean centers
        — one non-finite lane degrades to a reference re-solve instead
        of failing (or infecting) its whole batch. A request still
        non-finite after the reference pass fails with
        :class:`SolveFailed` (async: typed error on its future; sync:
        raised to the flushing caller)."""
        self._route_counter("salvaged", route.name).inc(len(bad))
        bucket = self._bucket_for(len(bad))
        prog = self._reference_program_for(route, bad, bucket)
        outs = self._gather_launch(route, prog, bad, bucket,
                                   reference=True)[0]
        res_list, centers, n_iters = self._scatter(route, prog, bad,
                                                   outs)[0][:3]
        max_iters = int(self._route_cfg(route).max_iters)
        doomed: Optional[BaseException] = None
        for lane, (p, r) in enumerate(zip(bad, res_list)):
            if not np.isfinite(centers[lane]).all():
                err = SolveFailed(
                    f"request {p.request_id}: non-finite centers even "
                    f"on the reference program")
                if not self._fail_request(p, err) and doomed is None:
                    doomed = err
                continue
            r.converged = bool(n_iters[lane] < max_iters)
            self._finish(route, results, r)
            if route.cacheable and self.cache_size > 0:
                fitted[p.key] = centers[lane]
                self._cache_put(p.key, centers[lane], p.hist)
        if doomed is not None:
            raise doomed

    def _run_bucket(self, route: RouteSpec, chunk: List[Any], bucket: int,
                    results: Dict[int, SegmentationResult],
                    fitted: Dict[bytes, np.ndarray]):
        fast = self._breaker_allows(route.name)
        prog = self._program_for(route, chunk, bucket) if fast else None
        max_iters = int(self._route_cfg(route).max_iters)
        mesh = self._mesh_for_bucket(bucket)
        shards = 1 if mesh is None else mesh.size
        with self.tracer.span("bucket",
                              annotate=("route", "bucket", "shards"),
                              route=route.name, bucket=bucket,
                              shards=shards, n=len(chunk), fused=fast,
                              requests=[p.request_id for p in chunk]):
            # Queue wait: each real lane's submit -> its batch's gather.
            now = time.perf_counter()
            waited = 0.0
            for p in chunk:
                sub = self._submit_t.get(p.request_id)
                if sub is not None:
                    waited += now - sub[0]
            self._queue_wait(route.name).inc(waited)
            # ``inputs`` stays referenced until the batch is done: freed
            # before the labels' device_get, it cost the closed-loop
            # histogram cell ~12% on a v5e chip (PERF.md §6).
            outs = None
            if fast:
                # Device-resident fast path: host-side stacking, ONE
                # jitted dispatch (ingest-binning + solve + defuzzify).
                # Launch failures surviving the retry budget advance the
                # breaker and degrade this chunk to the reference program.
                try:
                    outs, inputs, secs = self._gather_launch(
                        route, prog, chunk, bucket)
                except (ValueError, TypeError):
                    raise       # programming errors are not transient
                except Exception:
                    self._breaker_failure(route.name)
                    self._route_counter("degraded", route.name).inc()
                else:
                    self._breaker_success(route.name)
            if outs is None:
                prog = self._reference_program_for(route, chunk, bucket)
                outs, inputs, secs = self._gather_launch(
                    route, prog, chunk, bucket, reference=True)
            scattered, outs, unpack = self._scatter(route, prog, chunk, outs)
            res_list, centers, n_iters, total_iters, deltas = scattered
            if self._faults is not None:
                centers = np.asarray(self._faults.corrupt(
                    "solve", centers, route=route.name))
            finite = np.isfinite(
                centers.reshape(centers.shape[0], -1)).all(axis=1)
            n_iters = np.asarray(n_iters)
            if shards > 1:
                self._count_shards(route.name, n_iters, shards)
            bad: List[Any] = []
            for lane, (p, r) in enumerate(zip(chunk, res_list)):
                if not finite[lane]:
                    bad.append(p)
                    continue
                r.converged = bool(n_iters[lane] < max_iters)
                self._finish(route, results, r)
            # Accounting after the futures resolve: their clients go on.
            for stage, s in zip(("gather", "solve", "h2d", "materialize",
                                 "d2h"), secs + unpack):
                self._stage_seconds(route.name, stage).inc(s)
            self._route_counter("d2h_bytes", route.name).inc(
                sum(x.nbytes for x in jax.tree_util.tree_leaves(outs)))
            if bad:
                # Poisoned lanes (injected or real non-finite centers):
                # reference re-solve, healthy batchmates already
                # finished untouched above.
                with self.tracer.span("salvage", route=route.name,
                                      n=len(bad)):
                    self._salvage_requests(route, bad, results, fitted)
        self._route_counter("batches", route.name).inc()
        self._route_counter("images", route.name).inc(len(chunk))
        self._route_counter("padded", route.name).inc(bucket - len(chunk))
        self._route_counter("iters", route.name).inc(int(total_iters))
        self._occupancy_hist(route.name).record(len(chunk) / bucket)
        # Convergence telemetry: one sample per *real* lane (padding
        # lanes would skew the mix).
        h = self._iters_hist(route.name)
        for it in n_iters[:len(chunk)]:
            h.record(int(it))
        self.metrics.gauge("route.last_final_delta", route=route.name).set(
            float(np.max(deltas[:len(chunk)])))
        if route.cacheable and self.cache_size > 0:
            for lane, p in enumerate(chunk):
                if not finite[lane]:
                    continue    # poisoned centers must never enter the LRU
                fitted[p.key] = centers[lane]
                self._cache_put(p.key, centers[lane], p.hist)

    # -- cache -------------------------------------------------------------

    def _cache_get(self, key: bytes,
                   hist: Optional[np.ndarray] = None) -> Optional[np.ndarray]:
        if self.cache_size <= 0:
            return None
        entry = self._cache.get(key)
        if entry is not None:
            self._cache.move_to_end(key)
            return entry[0]
        if hist is None or self.cache_tol <= 0:
            return None
        # Nearest-match scan, most-recent first (the cache is small and a
        # 256-float L1 is trivial next to an FCM fit).
        q = self._normalize(hist)
        for k in reversed(self._cache):
            centers, dist = self._cache[k]
            if float(np.abs(dist - q).sum()) <= self.cache_tol:
                self._cache.move_to_end(k)
                return centers
        return None

    def _cache_put(self, key: bytes, centers: np.ndarray, hist: np.ndarray):
        if self.cache_size <= 0:
            return
        self._cache[key] = (np.asarray(centers), self._normalize(hist))
        self._cache.move_to_end(key)
        while len(self._cache) > self.cache_size:
            self._cache.popitem(last=False)

    # -- observability -----------------------------------------------------

    # Legacy per-route queue attributes (pre-registry API, still used by
    # tests and external monitors).
    @property
    def _queue(self) -> List[_Pending]:
        return self._queues["histogram"]

    @property
    def _pixel_queue(self) -> List[_PendingPixels]:
        return self._queues["pixel"]

    @property
    def _spatial_queue(self) -> List[_PendingSpatial]:
        return self._queues["spatial"]

    @property
    def _superpixel_queue(self) -> List[_PendingSuperpixel]:
        return self._queues["superpixel"]

    @property
    def queue_depth(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def stats(self) -> Dict[str, Any]:
        """The flat legacy stat keys (rendered from the metrics
        registry — the registry is the single source of truth) plus the
        per-route ``latency`` (submit->result percentiles) and
        ``convergence`` (iterations-to-converge) blocks. Everything in
        the returned dict is plain JSON-serializable."""
        s: Dict[str, Any] = {}
        s["requests"] = self.metrics.counter("requests").snapshot()
        s["cache_hits"] = self.metrics.counter("cache_hits").snapshot()
        for route in ROUTES.values():
            s[route.stat("seconds")] = \
                self._stage_seconds(route.name, "solve").snapshot()
            s[route.stat("ingest")] = \
                self._stage_seconds(route.name, "ingest").snapshot()
            s[route.stat("materialize")] = \
                self._stage_seconds(route.name, "materialize").snapshot()
            s[route.stat("compress")] = \
                self._stage_seconds(route.name, "compress").snapshot()
            for k in ("batches", "images", "padded", "iters"):
                s[route.stat(k)] = \
                    self._route_counter(k, route.name).snapshot()
        # Legacy aggregates: the pre-registry spatial counter, and
        # compress_seconds summed over routes (historically one global
        # key written by superpixel ingest; now per-route stage time).
        if "spatial" in ROUTES:
            s["spatial_requests"] = \
                self._route_counter("requests", "spatial").snapshot()
        s["compress_seconds"] = sum(
            self._stage_seconds(r.name, "compress").snapshot()
            for r in ROUTES.values())
        s["queue_depth"] = self.queue_depth
        s["cache_entries"] = len(self._cache)
        # Per-route request/cache-hit mix (only cacheable routes can hit,
        # but the dashboards want every column).
        s["method_requests"] = {
            r.name: self._route_counter("requests", r.name).snapshot()
            for r in ROUTES.values()}
        s["method_cache_hits"] = {
            r.name: self._route_counter("cache_hits", r.name).snapshot()
            for r in ROUTES.values()}
        # Hit rate over cacheable traffic only — the bypass routes must
        # not dilute it.
        cacheable = sum(s["method_requests"][r.name]
                        for r in ROUTES.values() if r.cacheable)
        s["cache_hit_rate"] = (s["cache_hits"] / cacheable
                               if cacheable else 0.0)
        fit_s = s.get("fit_seconds", 0.0)
        s["images_per_sec"] = (s.get("batched_images", 0) / fit_s
                               if fit_s > 0 else 0.0)
        # Per-route stage breakdown (ingest = submit-time validation,
        # gather = flush-time batch stacking, solve = the fenced device
        # dispatch, materialize = unpack / per-request labeling) — what
        # overhead regressions page on.
        s["stage_seconds"] = {
            r.name: {"ingest": s[r.stat("ingest")],
                     "gather": self._stage_seconds(r.name,
                                                   "gather").snapshot(),
                     "solve": s[r.stat("seconds")],
                     "materialize": s[r.stat("materialize")]}
            for r in ROUTES.values()}
        s["compiled_programs"] = len(self._programs)
        # Which registry kernels the compiled programs resolved, per
        # route: the device path a deployment actually serves on.
        impls: Dict[str, set] = {}
        for key, prog in list(self._programs.items()):
            impls.setdefault(key[0], set()).update(prog.impls)
        s["route_impls"] = {name: sorted(v) for name, v in impls.items()}
        # Per-route submit->result latency percentiles and convergence
        # mix — the two new observability blocks.
        s["latency"] = {r.name: self._latency_hist(r.name).snapshot()
                        for r in ROUTES.values()}
        s["convergence"] = {}
        for r in ROUTES.values():
            h = self._iters_hist(r.name)
            g = self.metrics.peek("route.last_final_delta", route=r.name)
            s["convergence"][r.name] = {
                "lanes": h.count,
                "mean_iters": h.mean,
                "p50_iters": h.quantile(0.50),
                "p99_iters": h.quantile(0.99),
                "last_final_delta": g.snapshot() if g else None,
            }
        # Admission telemetry: live queue depths, per-launch batch
        # occupancy (real lanes / bucket), deadline misses, and the
        # count of futures still awaiting results.
        s["queue_depth_by_route"] = {
            r.name: len(self._queues.get(r.name, ()))
            for r in ROUTES.values()}
        s["batch_occupancy"] = {
            r.name: self._occupancy_hist(r.name).snapshot()
            for r in ROUTES.values()}
        s["deadline_expired"] = {
            r.name: self._route_counter("deadline_expired",
                                        r.name).snapshot()
            for r in ROUTES.values()}
        s["pending_futures"] = len(self._futures)
        # Fault-tolerance telemetry: the graceful-degradation ladder's
        # per-route counters plus breaker state and flusher health.
        with self._lock:
            breaker_state = {name: b["state"]
                             for name, b in self._breakers.items()}
        s["fault_tolerance"] = {
            "retries": {r.name: self._route_counter(
                "retries", r.name).snapshot() for r in ROUTES.values()},
            "shed": {r.name: self._route_counter(
                "shed", r.name).snapshot() for r in ROUTES.values()},
            "salvaged": {r.name: self._route_counter(
                "salvaged", r.name).snapshot() for r in ROUTES.values()},
            "degraded": {r.name: self._route_counter(
                "degraded", r.name).snapshot() for r in ROUTES.values()},
            "breaker_trips": {r.name: self._route_counter(
                "breaker_trips", r.name).snapshot()
                for r in ROUTES.values()},
            "invalid_input": {r.name: self._route_counter(
                "invalid_input", r.name).snapshot()
                for r in ROUTES.values()},
            "breaker_state": breaker_state,
            "flusher_restarts":
                self.metrics.counter("flusher.restarts").snapshot(),
            "flusher_kills": self._flusher_kills,
        }
        s["faults"] = (self._faults.snapshot() if self._faults is not None
                       else FI.clean_snapshot())
        return obs.json_safe(s)

    def healthy(self) -> bool:
        """Liveness: no route breaker stuck open AND (if async traffic
        is in flight) the flusher thread is alive. A tripped breaker is
        *degraded* — requests still complete via the reference fallback
        — so it flips readiness, not liveness; ``healthy()`` is False
        only when async requests are pending with no live flusher to
        drain them (and none can be restarted because we're shut down)."""
        with self._lock:
            if self._closed:
                return False
            if sum(self._async_n.values()) > 0 and (
                    self._flusher is None
                    or not self._flusher.is_alive()):
                # submit_async re-ensures the flusher, so a dead thread
                # here is only unhealthy once restarts are impossible.
                return False
        return True

    def readiness(self) -> Dict[str, Any]:
        """One JSON-safe health snapshot for probes: overall liveness,
        per-route breaker state, flusher aliveness/restarts, and queue
        pressure against the overload limit."""
        with self._lock:
            breaker_state = {r.name: self._breaker(r.name)["state"]
                             for r in ROUTES.values()}
            flusher_alive = (self._flusher is not None
                             and self._flusher.is_alive())
            depth = self._qtotal
        return obs.json_safe({
            "healthy": self.healthy(),
            "ready": not self._closed
            and all(st != "open" for st in breaker_state.values()),
            "breaker_state": breaker_state,
            "flusher_alive": flusher_alive,
            "flusher_restarts":
                self.metrics.counter("flusher.restarts").snapshot(),
            "flusher_kills": self._flusher_kills,
            "queue_depth": depth,
            "max_queue_depth": self.max_queue_depth,
        })

    def reset_stats(self) -> None:
        """Zero every counter/gauge/histogram and drop the trace ring;
        registered metric keys survive so the stats schema is unchanged
        after a reset (dashboards keep their columns)."""
        self.metrics.reset()
        self.tracer.clear()
        self._submit_t.clear()

    def snapshot(self) -> Dict[str, Any]:
        """One JSON-serializable observability dump: the stats dict, the
        raw metrics registry, and the recent flush traces."""
        return obs.json_safe({
            "stats": self.stats(),
            "metrics": self.metrics.snapshot(),
            "traces": self.tracer.traces(),
        })
