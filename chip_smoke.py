"""Smoke run of the FCM serving path on a TPU.

One chip (the default) drives the paper's configuration through the
engine a user would call and checks what comes back:

  a. the device line;
  b. serving: ``FCMServeEngine.submit_async`` of 64 slices of 217x181
     on each route (histogram, pixel, spatial, superpixel), then one
     more request alone, so that bucket 64 and bucket 1 both run;
  c. comparison: the same requests solved by ``solve_batched`` on the
     ``reference`` backend (plain XLA, no Pallas kernel): centers,
     iterations, labels and per-class DSC;
  d. the paper's case: ``solve`` on a 1 MiB phantom, against the
     reference backend in the same way.

It fails when the device is not a TPU, when any phase fails a bound,
and when a fallback ran in place of the device path: a retry, a
degraded launch, a salvaged request or lane, a breaker that tripped, or
a route that resolved another kernel than the registry picks for a TPU
at that shape.

``--chips 4`` runs only the four-chip phase: a mesh engine against a
single-device engine on the histogram, pixel and spatial routes, and
``fit_sharded`` against ``solve``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips

Timings printed on the way are smoke timings, not metrics. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro  # noqa: E402
from repro import obs  # noqa: E402
from repro.configs.fcm_brainweb import make_config  # noqa: E402
from repro.core import distributed as D  # noqa: E402
from repro.core import fcm as F  # noqa: E402
from repro.core import solver as SV  # noqa: E402
from repro.core import spatial as SP  # noqa: E402
from repro.core.batched import hist_rows  # noqa: E402
from repro.data import phantom as PH  # noqa: E402
from repro.kernels import fcm_resident as KR  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.serving.fcm_engine import ROUTES, FCMServeEngine  # noqa: E402
from repro.superpixel import pipeline as SX  # noqa: E402
from repro.superpixel import slic as SL  # noqa: E402

H, W = 217, 181                  # one BrainWeb slice, the paper's geometry
N_REQ = 64                       # the engine's largest bucket
PAPER_BYTES = 1 << 20            # the paper's largest dataset (Table 3)
SEED = 0

# Bounds against the reference backend. Both backends stop once a step
# moves the centers less than the lane's tolerance, so the centers may
# differ by about that much and no bound can be tighter.
CENTER_TOLS = 2.0                # max |v - v_ref| in lane tolerances
ITER_SLACK = 2                   # max |iters - iters_ref|
DSC_SLACK = 0.01                 # max |DSC - DSC_ref| per class


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def timing(what: str, cold: float, warm: float) -> None:
    log(f"  smoke timing (not a metric): {what}: first run {cold:.3f} s, "
        f"warm {warm:.3f} s, compile ~{max(cold - warm, 0.0):.3f} s")


def twice(fn):
    """Run ``fn`` cold (compiles) and warm; returns the warm result."""
    t0 = time.perf_counter()
    fn()
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    return out, cold, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Data and configuration
# ---------------------------------------------------------------------------

def make_requests(routes):
    """N_REQ + 1 seeded (image, ground truth) pairs per route, with the
    anatomy drifting over slice positions."""
    make = {"histogram": PH.phantom_slice, "pixel": PH.phantom_slice,
            "spatial": PH.noisy_phantom_slice,
            "superpixel": PH.phantom_slice_rgb}
    return {r: [make[r](H, W, slice_pos=0.3 + 0.4 * i / N_REQ,
                        seed=SEED + i) for i in range(N_REQ + 1)]
            for r in routes}


def make_engine(job, mesh=None):
    # No LRU: every request must reach the kernels. A long max_wait
    # keeps each route's 64 requests in one bucket.
    return FCMServeEngine(job.fcm, batch_sizes=job.serving_batch_sizes,
                          spatial_cfg=job.spatial,
                          superpixel_cfg=job.superpixel, cache_size=0,
                          tracing=False, max_wait_ms=60_000.0, mesh=mesh)


def route_cfg(job, route):
    return {"spatial": job.spatial, "superpixel": job.superpixel}.get(
        route, job.fcm)


def serve(eng, route, items):
    """64 requests in one bucket, then one alone; returns the results."""
    futs = [eng.submit_async(img, method=route) for img, _ in items[:-1]]
    eng.drain()
    futs.append(eng.submit_async(items[-1][0], method=route))
    eng.drain()
    return [f.result(timeout=600) for f in futs]


def check_no_fallback(eng, tag: str) -> None:
    ft = eng.stats()["fault_tolerance"]
    for k in ("retries", "degraded", "salvaged", "breaker_trips"):
        check(not any(ft[k].values()), f"{tag}: {k} {ft[k]}")
    states = eng.readiness()["breaker_state"]
    check(all(s == "closed" for s in states.values()),
          f"{tag}: breakers {states}")


def counters(reg, name: str):
    """[(labels dict, value)] of one counter family's nonzero members."""
    out = []
    for key, v in reg.snapshot()["counters"].items():
        if key.startswith(name + "{") and v:
            out.append((dict(kv.split("=") for kv in
                             key[len(name) + 1:-1].split(",")), v))
    return out


def expected_impls(job, platform: str):
    """What the registry picks for each route's launch at this shape."""
    c = job.fcm.n_clusters
    gy, gx = SL.grid_shape(H, W, job.superpixel.n_segments)

    def pick(kind, **kw):
        return f"{kind}/" + kops.select_step(kind, platform=platform,
                                             **kw).name
    return {
        "histogram": {"bin/pallas", pick("flat", batched=True, n_rows=256,
                                         c=c)},
        "pixel": {pick("flat", batched=True, n_rows=H * W, c=c),
                  pick("labels")},
        "spatial": {pick("stencil", batched=True,
                         n_rows=KR.stencil_pixels((H, W)), c=c)},
        "superpixel": {pick("slic_assign"),
                       pick("flat", batched=True, n_rows=gy * gx, n_feat=3,
                            c=c)},
    }


# ---------------------------------------------------------------------------
# Reference backend and comparisons
# ---------------------------------------------------------------------------

def reference_problem(job, route, items):
    """The batched problem of these requests, built without the route:
    (problem, per-request superpixel compressions or None)."""
    cfg = route_cfg(job, route)
    imgs = np.stack([img for img, _ in items])
    comps = None
    if route == "histogram":
        hists = np.stack([np.bincount(im.ravel(), minlength=256)
                          for im in imgs]).astype(np.float32)
        problem = SV.batch_problems(hist_rows(jnp.asarray(hists)),
                                    jnp.asarray(hists), cfg=cfg)
    elif route == "pixel":
        problem = SV.batch_problems(
            jnp.asarray(imgs.reshape(len(imgs), -1), jnp.float32), cfg=cfg)
    elif route == "spatial":
        problem = SV.batch_problems(
            jnp.asarray(imgs, jnp.float32),
            stencil=SV.StencilSpec(cfg.alpha, cfg.neighbors), cfg=cfg)
    else:
        comps = [SX.compress(im, cfg, use_pallas=False) for im in imgs]
        problem = SV.batch_problems(
            jnp.stack([cp.features for cp in comps]),
            jnp.stack([cp.weights for cp in comps]), cfg=cfg)
    return problem, comps


def reference(job, route, items):
    """The same requests on ``solve_batched(backend="reference")``:
    returns (problem, result, per-request reference labels)."""
    cfg = route_cfg(job, route)
    imgs = np.stack([img for img, _ in items])
    problem, comps = reference_problem(job, route, items)
    res = SV.solve_batched(problem, cfg, backend="reference")
    cen = np.asarray(res.centers)
    if route == "superpixel":
        labels = [np.asarray(F.labels_from_centers(cp.features, v))[
            np.asarray(cp.label_map)] for cp, v in zip(comps, cen)]
    elif route == "spatial":
        labels = list(spatial_labels(cfg, imgs, cen))
    else:
        labels = [np.asarray(F.labels_from_centers(
            jnp.asarray(im.ravel(), jnp.float32), v)).reshape(im.shape)
            for im, v in zip(imgs, cen)]
    return problem, res, labels


def spatial_labels(cfg, imgs, centers):
    fn = jax.jit(jax.vmap(lambda im, v: jnp.argmax(SP.spatial_membership(
        im, v, cfg.m, cfg.alpha, cfg.neighbors), axis=0).astype(jnp.int32)))
    return np.asarray(fn(jnp.asarray(imgs, jnp.float32),
                         jnp.asarray(centers)))


def own_labels(job, route, items, results):
    """Each request's labels recomputed by the plain-XLA reference from
    the centers the route returned (the route's defuzzify, checked)."""
    cfg = route_cfg(job, route)
    if route == "spatial":
        return list(spatial_labels(cfg, np.stack([im for im, _ in items]),
                                   np.stack([r.centers for r in results])))
    out = []
    for (img, _), r in zip(items, results):
        if route == "superpixel":
            cp = SX.compress(img, cfg)          # the route's own compression
            out.append(np.asarray(F.labels_from_centers(
                cp.features, jnp.asarray(r.centers)))[
                    np.asarray(cp.label_map)])
        else:
            out.append(np.asarray(F.labels_from_centers(
                jnp.asarray(img.ravel(), jnp.float32),
                jnp.asarray(r.centers))).reshape(img.shape))
    return out


def mean_dsc(route, labels, centers, gts):
    """Per-class DSC against the phantom's ground truth, averaged over
    the requests (clusters matched to classes by their centers)."""
    rows = []
    for lab, cen, gt in zip(labels, centers, gts):
        if route == "superpixel":
            lab = PH.match_labels_to_means(lab, cen, PH.CLASS_MEANS_RGB)
        else:
            lab = PH.match_labels_to_classes(lab, cen)
        rows.append(PH.dice_per_class(lab, gt))
    return np.mean(rows, axis=0)


def compare(tag, centers, iters, labels, gts, ref_centers, ref_iters,
            ref_labels, lane_tol, route):
    """Print and check one route's agreement with the reference."""
    centers, ref_centers = np.asarray(centers), np.asarray(ref_centers)
    b = centers.shape[0]
    dev = (np.abs(centers - ref_centers).reshape(b, -1).max(axis=1)
           / np.asarray(lane_tol))
    di = np.abs(np.asarray(iters) - np.asarray(ref_iters))
    dsc = mean_dsc(route, labels, centers, gts)
    dsc_ref = mean_dsc(route, ref_labels, ref_centers, gts)
    log(f"  {tag}: max center deviation {dev.max():.4f} x tol "
        f"(bound {CENTER_TOLS}), max |iters - ref| {int(di.max())} "
        f"(bound {ITER_SLACK}), mean iters {np.mean(iters):.2f} vs "
        f"{np.mean(ref_iters):.2f}")
    log(f"  {tag}: per-class DSC {np.round(dsc, 4).tolist()} vs reference "
        f"{np.round(dsc_ref, 4).tolist()}")
    check(np.isfinite(centers).all(), f"{tag}: non-finite centers")
    check(dev.max() <= CENTER_TOLS, f"{tag}: centers {dev.max():.3f} tol")
    check(di.max() <= ITER_SLACK, f"{tag}: iterations differ by {di.max()}")
    check(np.abs(dsc - dsc_ref).max() <= DSC_SLACK,
          f"{tag}: DSC {dsc} vs reference {dsc_ref}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_serving(job, platform: str):
    """Phases b and c: serve every route, then compare with the
    reference backend."""
    routes = ("histogram", "pixel", "spatial", "superpixel")
    reqs = make_requests(routes)
    want = expected_impls(job, platform)
    eng = make_engine(job)
    served = {}
    with obs.scoped_registry() as reg:
        for route in routes:
            served[route], cold, warm = twice(
                lambda: serve(eng, route, reqs[route]))
            log(f"b. {route}: served {len(served[route])} requests")
            timing(f"serve {route} ({N_REQ} + 1 requests)", cold, warm)
    check_no_fallback(eng, "serving")
    check(not counters(reg, "solver.salvaged_lanes"),
          f"salvaged lanes {counters(reg, 'solver.salvaged_lanes')}")
    got = {r: set(v) for r, v in eng.stats()["route_impls"].items()}
    # The superpixel route compresses on ingest, which records the SLIC
    # impl it ran; its program names the solve's.
    got["superpixel"] = got.get("superpixel", set()) | {
        f"slic_assign/{lab['impl']}" for lab, _ in counters(reg, "slic.fits")}
    for route in routes:
        log(f"   {route} resolved {sorted(got.get(route, ()))}, "
            f"registry picks {sorted(want[route])} on {platform}")
        check(got.get(route) == want[route],
              f"{route}: resolved {got.get(route)} not {want[route]}")
        check(not any(i.endswith("/reference") for i in want[route]),
              f"{route}: the registry picks a reference impl")
    eng.shutdown()

    for route in routes:
        items, res = reqs[route], served[route]
        check(all(np.isfinite(r.centers).all() for r in res),
              f"{route}: a request resolved with non-finite centers")
        (problem, ref, ref_labels), cold, warm = twice(
            lambda: reference(job, route, items))
        timing(f"reference solve_batched {route}", cold, warm)
        own = own_labels(job, route, items, res)
        same = [np.array_equal(r.labels, o) for r, o in zip(res, own)]
        log(f"c. {route}: {sum(same)}/{len(same)} label maps equal the "
            f"reference labels of the route's own centers")
        check(all(same), f"{route}: label maps differ from the reference "
                         f"labels of their own centers")
        compare(f"c. {route}", [r.centers for r in res],
                [r.n_iters for r in res], [r.labels for r in res],
                [gt for _, gt in items], ref.centers, ref.n_iters,
                ref_labels, SV.lane_tolerances(problem,
                                               route_cfg(job, route).eps),
                route)


def phase_paper(job, platform: str):
    """Phase d: the paper's 1 MiB case through ``solve``."""
    img, gt = PH.phantom_of_bytes(PAPER_BYTES, seed=SEED)
    x = jnp.asarray(img, jnp.float32)
    problem = SV.pixel_problem(x, job.fcm)
    with obs.scoped_registry() as reg:
        res, cold, warm = twice(lambda: SV.solve(problem, job.fcm))
    timing("solve 1 MiB", cold, warm)
    want = "flat/" + kops.select_step("flat", platform=platform,
                                      n_rows=x.shape[0],
                                      c=job.fcm.n_clusters).name
    got = {f"{lab['kind']}/{lab['impl']}"
           for lab, _ in counters(reg, "solver.solves")}
    log(f"d. paper case: {x.shape[0]} pixels, resolved {sorted(got)}, "
        f"registry picks {want} on {platform}")
    check(got == {want} and want != "flat/reference",
          f"paper case resolved {got}, not {want}")
    ref, cold, warm = twice(
        lambda: SV.solve(problem, job.fcm, backend="reference"))
    timing("reference solve 1 MiB", cold, warm)
    own = np.asarray(F.labels_from_centers(x, res.centers))
    same = np.array_equal(np.asarray(res.labels), own)
    log(f"d. paper case: labels equal the reference labels of its own "
        f"centers: {same}")
    check(same, "paper case: labels differ from its centers' labels")
    tol = SV.lane_tolerances(SV.batch_problems(x[None], cfg=job.fcm),
                             job.fcm.eps)
    compare("d. paper case", [res.centers], [res.n_iters],
            [np.asarray(res.labels)], [gt], [ref.centers], [ref.n_iters],
            [np.asarray(ref.labels)], tol, "pixel")


def phase_mesh(job, platform: str, n_chips: int):
    """Phase g: a mesh engine against a single-device engine, and
    ``fit_sharded`` against ``solve``."""
    mesh = jax.make_mesh((n_chips,), ("data",))
    routes = ("histogram", "pixel", "spatial")
    reqs = make_requests(routes)
    single, meshed = make_engine(job), make_engine(job, mesh=mesh)
    for route in routes:
        ref, cold_s, warm_s = twice(lambda: serve(single, route,
                                                  reqs[route]))
        got, cold_m, warm_m = twice(lambda: serve(meshed, route,
                                                  reqs[route]))
        timing(f"serve {route} on one device", cold_s, warm_s)
        timing(f"serve {route} on a {n_chips}-device mesh", cold_m, warm_m)
        bitwise = sum(np.array_equal(a.centers, b.centers)
                      and np.array_equal(a.labels, b.labels)
                      and a.n_iters == b.n_iters for a, b in zip(got, ref))
        log(f"g. {route}: {bitwise}/{len(ref)} requests bitwise identical "
            f"between the mesh and the single-device engine")
        tol = SV.lane_tolerances(reference_problem(job, route,
                                                   reqs[route])[0],
                                 route_cfg(job, route).eps)
        compare(f"g. {route} mesh vs one device", [a.centers for a in got],
                [a.n_iters for a in got], [a.labels for a in got],
                [gt for _, gt in reqs[route]], [b.centers for b in ref],
                [b.n_iters for b in ref], [b.labels for b in ref], tol,
                route)
    for tag, eng in (("single", single), ("mesh", meshed)):
        check_no_fallback(eng, tag)
    impls = meshed.stats()["route_impls"]
    want = expected_impls(job, platform)
    for route in routes:
        check(set(impls.get(route, ())) == want[route],
              f"mesh {route}: resolved {impls.get(route)}")

    # The sharded launch itself: its outputs span every device.
    route = ROUTES["pixel"]
    chunk = [route.ingest(meshed, img, i)
             for i, (img, _) in enumerate(reqs["pixel"][:N_REQ])]
    prog = meshed._program_for(route, chunk, N_REQ)
    outs = prog.launch(*prog.gather(meshed, chunk, N_REQ))
    spans = {len(o.sharding.device_set) for o in jax.tree.leaves(outs)
             if o.ndim}
    log(f"g. sharded pixel launch: outputs span {sorted(spans)} devices")
    check(spans == {n_chips}, f"sharded outputs span {spans} devices")
    single.shutdown()
    meshed.shutdown()

    img, gt = PH.phantom_of_bytes(PAPER_BYTES, seed=SEED)
    x = jnp.asarray(img, jnp.float32)
    (sharded, one), cold, warm = twice(lambda: (
        D.fit_sharded(x, mesh, job.fcm),
        SV.solve(SV.pixel_problem(x, job.fcm), job.fcm)))
    timing("fit_sharded + solve 1 MiB", cold, warm)
    agree = np.mean(np.asarray(sharded.labels) == np.asarray(one.labels))
    log(f"g. fit_sharded: {agree:.6f} of labels agree with solve")
    tol = SV.lane_tolerances(SV.batch_problems(x[None], cfg=job.fcm),
                             job.fcm.eps)
    compare("g. fit_sharded vs solve", [sharded.centers],
            [sharded.n_iters], [np.asarray(sharded.labels)], [gt],
            [one.centers], [one.n_iters], [np.asarray(one.labels)], tol,
            "pixel")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the mesh and fit_sharded phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    n = len(jax.devices())
    log(f"a. jax {jax.__version__}, device_kind {dev.device_kind!r}, "
        f"platform {dev.platform}, {n} devices")
    if dev.platform != "tpu":
        print("FAIL: no TPU found; this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    if n < args.chips:
        print(f"FAIL: --chips {args.chips} but {n} devices",
              file=sys.stderr)
        return 2

    log(f"   compile cache: {repro.enable_compile_cache()}")
    job = make_config()
    try:
        if args.chips == 1:
            phase_serving(job, dev.platform)
            phase_paper(job, dev.platform)
        else:
            phase_mesh(job, dev.platform, args.chips)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
