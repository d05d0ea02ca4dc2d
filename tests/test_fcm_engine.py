"""FCMServeEngine: bucketing, caching, correctness of served labels
against the single-image histogram fit, and the device-resident route
programs (single-dispatch serving, program-cache lifecycle)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fcm as F
from repro.core import solver as SV
from repro.data import phantom
from repro.serving.fcm_engine import FCMServeEngine


CFG = F.FCMConfig(max_iters=300)


@pytest.fixture(scope="module")
def volume():
    """12 heterogeneous-size slices (volumetric traffic)."""
    return [phantom.phantom_slice(64 + 8 * (z % 4), 96,
                                  slice_pos=0.3 + 0.4 * z / 12,
                                  noise=4.0, seed=z)[0] for z in range(12)]


def test_served_labels_match_single_image_fit(volume):
    eng = FCMServeEngine(CFG, batch_sizes=(1, 8, 64), cache_size=0)
    results = eng.segment(volume)
    assert [r.request_id for r in results] == list(range(12))
    for img, r in zip(volume, results):
        assert r.labels.shape == img.shape
        x = img.ravel().astype(np.float32)
        single = SV.solve(SV.histogram_problem(x, CFG), backend="reference")
        np.testing.assert_allclose(r.centers, np.asarray(single.centers),
                                   atol=1e-4)
        lab = F.labels_from_centers(jnp.asarray(x), single.centers)
        assert (r.labels == np.asarray(lab).reshape(img.shape)).all()
        assert r.n_iters == single.n_iters


def test_bucketing_pads_to_fixed_shapes(volume):
    eng = FCMServeEngine(CFG, batch_sizes=(4, 16), cache_size=0)
    eng.segment(volume)                      # 12 requests -> one 16-bucket
    s = eng.stats()
    assert s["batches"] == 1
    assert s["padded_lanes"] == 4
    assert s["batched_images"] == 12
    assert s["queue_depth"] == 0


def test_oversize_flush_splits_into_max_buckets(volume):
    eng = FCMServeEngine(CFG, batch_sizes=(4,), cache_size=0)
    eng.segment(volume)                      # 12 requests -> three 4-buckets
    assert eng.stats()["batches"] == 3


def test_cache_hit_on_identical_resubmission(volume):
    eng = FCMServeEngine(CFG, batch_sizes=(1, 8, 64))
    first = eng.segment([volume[0]])[0]
    assert not first.cache_hit
    again = eng.segment([volume[0]])[0]
    assert again.cache_hit and again.n_iters == 0
    assert (again.labels == first.labels).all()
    np.testing.assert_allclose(again.centers, first.centers, atol=0)
    assert eng.stats()["cache_hits"] == 1


def test_intra_flush_dedup(volume):
    eng = FCMServeEngine(CFG, batch_sizes=(1, 8, 64))
    results = eng.segment([volume[0]] * 5)   # 5 identical in one flush
    s = eng.stats()
    assert s["batched_images"] == 1          # one representative fit
    assert s["cache_hits"] == 4
    assert all((r.labels == results[0].labels).all() for r in results)


def test_duplicates_with_cache_disabled_all_answered(volume):
    """Regression: with cache_size=0, duplicate submissions in one flush
    used to collapse in the dedup dict and lose requests."""
    eng = FCMServeEngine(CFG, batch_sizes=(1, 8), cache_size=0)
    results = eng.segment([volume[0]] * 3)
    assert len(results) == 3
    assert all((r.labels == results[0].labels).all() for r in results)


def test_duplicates_survive_intra_flush_lru_eviction(volume):
    """Regression: a duplicate's centers come from this flush's fits, not
    the LRU cache, which may already have evicted the representative."""
    eng = FCMServeEngine(CFG, batch_sizes=(8,), cache_size=1, cache_tol=0.0)
    imgs = [phantom.phantom_slice(64, 64, noise=2.0 + 3 * i, seed=i)[0]
            for i in range(3)]
    results = eng.segment([imgs[0], imgs[1], imgs[2], imgs[0]])
    assert len(results) == 4
    np.testing.assert_allclose(results[3].centers, results[0].centers,
                               atol=0)


def test_near_identical_histograms_hit_cache():
    # Same anatomy, fresh noise draw (L1 ~ 0.08 between normalized
    # histograms): the nearest-match scan must serve it from cache.
    a = phantom.phantom_slice(96, 96, slice_pos=0.5, noise=4.0, seed=1)[0]
    b, gt = phantom.phantom_slice(96, 96, slice_pos=0.5, noise=4.0, seed=2)
    eng = FCMServeEngine(CFG)
    ra = eng.segment([a])[0]
    rb = eng.segment([b])[0]
    assert not ra.cache_hit and rb.cache_hit
    # served-from-cache labels are still per-pixel correct for image b
    pred = phantom.match_labels_to_classes(rb.labels, rb.centers)
    assert min(phantom.dice_per_class(pred, gt)) > 0.80


def test_distinct_content_does_not_hit_cache():
    # Different anatomy/noise (L1 ~ 0.5) must NOT near-match.
    a = phantom.phantom_slice(96, 96, slice_pos=0.5, noise=4.0, seed=1)[0]
    b = phantom.phantom_slice(96, 96, slice_pos=0.9, noise=8.0, seed=2)[0]
    eng = FCMServeEngine(CFG)
    eng.segment([a])
    assert not eng.segment([b])[0].cache_hit


def test_lru_eviction():
    eng = FCMServeEngine(CFG, cache_size=2)
    imgs = [phantom.phantom_slice(64, 64, noise=2.0 + 3 * i, seed=i)[0]
            for i in range(3)]
    eng.segment(imgs)                        # fills + evicts oldest
    assert eng.stats()["cache_entries"] == 2
    assert eng.segment([imgs[0]])[0].cache_hit is False   # evicted
    assert eng.segment([imgs[2]])[0].cache_hit is True    # still resident


def test_spatial_route_bypasses_histogram_cache():
    """method="spatial" requests carry full pixel payloads around the
    LRU cache; histogram requests in the same flush still hit it."""
    eng = FCMServeEngine(CFG)
    img, _ = phantom.noisy_phantom_slice(48, 48, noise=10.0, impulse=0.05,
                                         seed=0)
    first = eng.segment([img])[0]            # histogram fit, fills cache
    assert first.method == "histogram"
    hits0 = eng.stats()["cache_hits"]
    entries0 = eng.stats()["cache_entries"]

    # Mixed batch: one identical histogram request + one spatial request.
    rid_h = eng.submit(img)
    rid_s = eng.submit(img, method="spatial")
    assert eng.queue_depth == 2
    res = {r.request_id: r for r in eng.flush()}
    assert eng.queue_depth == 0
    assert res[rid_h].cache_hit and res[rid_h].method == "histogram"
    sp = res[rid_s]
    assert sp.method == "spatial"
    assert not sp.cache_hit and sp.n_iters > 0
    assert sp.labels.shape == img.shape

    s = eng.stats()
    assert s["cache_hits"] == hits0 + 1      # only the histogram request
    assert s["cache_entries"] == entries0    # spatial never populated it
    assert s["spatial_requests"] == 1
    assert s["spatial_iters"] == sp.n_iters

    # An identical spatial resubmission must run the fit again — pixel
    # positions matter, histogram identity is not segmentation identity.
    sp2 = eng.segment([img], method="spatial")[0]
    assert not sp2.cache_hit and sp2.n_iters > 0
    assert eng.stats()["cache_hits"] == hits0 + 1
    np.testing.assert_allclose(sp2.centers, sp.centers, atol=1e-5)
    assert (sp2.labels == sp.labels).all()


def test_spatial_results_match_direct_fit_spatial():
    eng = FCMServeEngine(CFG)
    img, _ = phantom.noisy_phantom_slice(40, 56, noise=12.0, impulse=0.05,
                                         seed=3)
    served = eng.segment([img], method="spatial")[0]
    direct = SV.solve(SV.spatial_problem(img.astype(np.float32),
                                         eng.spatial_cfg), eng.spatial_cfg)
    np.testing.assert_allclose(served.centers, np.asarray(direct.centers),
                               atol=1e-5)
    assert (served.labels == np.asarray(direct.labels)).all()
    assert served.n_iters == direct.n_iters


def test_spatial_cache_hit_rate_counts_cacheable_traffic_only():
    eng = FCMServeEngine(CFG)
    img, _ = phantom.noisy_phantom_slice(32, 32, seed=1)
    eng.segment([img])                       # miss, fills cache
    eng.segment([img])                       # hit
    eng.segment([img], method="spatial")     # must not dilute the rate
    assert eng.stats()["cache_hit_rate"] == 0.5


def test_superpixel_route_serves_color_and_bypasses_cache():
    """method="superpixel" handles (H, W, D) payloads the histogram
    route cannot represent, and never touches the 1-D LRU."""
    eng = FCMServeEngine(CFG)
    img, gt = phantom.phantom_slice_rgb(96, 96, noise=4.0, seed=1)
    entries0 = eng.stats()["cache_entries"]
    res = eng.segment([img], method="superpixel")[0]
    assert res.method == "superpixel"
    assert not res.cache_hit and res.n_iters > 0
    assert res.labels.shape == (96, 96)
    assert res.centers.shape == (CFG.n_clusters, 3)
    pred = phantom.match_labels_to_means(res.labels, res.centers,
                                         phantom.CLASS_MEANS_RGB)
    assert min(phantom.dice_per_class(pred, gt)) > 0.9
    s = eng.stats()
    assert s["cache_entries"] == entries0       # never populated the LRU
    assert s["cache_hits"] == 0
    # resubmission runs the fit again (no vector cache yet, by design)
    again = eng.segment([img], method="superpixel")[0]
    assert not again.cache_hit and again.n_iters > 0
    assert (again.labels == res.labels).all()


def test_superpixel_bucket_matches_single_fits():
    """A flushed superpixel batch (with pad lanes) gives each request the
    centers a solo fit of its compressed payload would."""
    eng = FCMServeEngine(CFG, batch_sizes=(4,))
    imgs = [phantom.phantom_slice_rgb(64, 64, noise=3.0 + 2 * i, seed=i)[0]
            for i in range(3)]
    ids = [eng.submit(im, method="superpixel") for im in imgs]
    pend = {q.request_id: q for q in eng._superpixel_queue}
    by_id = {r.request_id: r for r in eng.flush()}
    s = eng.stats()
    assert s["superpixel_batches"] == 1 and s["superpixel_padded_lanes"] == 1
    for rid in ids:
        solo = SV.solve(SV.vector_problem(pend[rid].features,
                                          pend[rid].weights, CFG),
                        backend="reference")
        np.testing.assert_allclose(by_id[rid].centers,
                                   np.asarray(solo.centers), atol=1e-3)
        assert by_id[rid].n_iters == solo.n_iters


def test_superpixel_fit_honors_superpixel_cfg():
    """Regression: the bucket fit must run with the caller's
    superpixel_cfg hyper-parameters (here n_clusters=3), not self.cfg."""
    from repro.superpixel.pipeline import SuperpixelFCMConfig

    sp_cfg = SuperpixelFCMConfig(n_clusters=3, n_segments=48)
    eng = FCMServeEngine(CFG, superpixel_cfg=sp_cfg)
    img, _ = phantom.phantom_slice_rgb(64, 64, seed=4)
    res = eng.segment([img], method="superpixel")[0]
    assert res.centers.shape == (3, 3)
    assert set(np.unique(res.labels)) <= {0, 1, 2}


def test_pixel_route_matches_fit_fused():
    eng = FCMServeEngine(CFG)
    img, _ = phantom.phantom_slice(48, 56, seed=2)
    res = eng.segment([img], method="pixel")[0]
    direct = SV.solve(SV.pixel_problem(img.ravel().astype(np.float32),
                                       CFG), backend="reference")
    assert res.method == "pixel"
    np.testing.assert_allclose(res.centers, np.asarray(direct.centers),
                               atol=1e-5)
    assert (res.labels == np.asarray(direct.labels).reshape(48, 56)).all()


def test_per_method_counters_increment():
    """The stats() route mix: every submit bumps its method's request
    counter, and only histogram traffic ever bumps a cache-hit one."""
    eng = FCMServeEngine(CFG)
    s = eng.stats()
    assert s["method_requests"] == {
        "histogram": 0, "pixel": 0, "spatial": 0, "superpixel": 0}
    assert s["method_cache_hits"] == {
        "histogram": 0, "pixel": 0, "spatial": 0, "superpixel": 0}

    gray, _ = phantom.phantom_slice(48, 48, seed=0)
    rgb, _ = phantom.phantom_slice_rgb(48, 48, seed=0)
    eng.segment([gray])                          # histogram miss
    eng.segment([gray])                          # histogram hit
    eng.segment([gray, gray])                    # hit + intra-flush... both hit
    eng.segment([gray], method="pixel")
    eng.segment([gray], method="spatial")
    eng.segment([rgb], method="superpixel")
    eng.segment([rgb], method="superpixel")      # no cache for vectors

    s = eng.stats()
    assert s["method_requests"] == {
        "histogram": 4, "pixel": 1, "spatial": 1, "superpixel": 2}
    assert s["method_cache_hits"] == {
        "histogram": 3, "pixel": 0, "spatial": 0, "superpixel": 0}
    assert s["cache_hits"] == 3                  # legacy aggregate agrees
    assert s["requests"] == 8
    # hit rate is over histogram traffic only
    assert s["cache_hit_rate"] == pytest.approx(3 / 4)


def test_bad_pixel_request_rejected_at_ingest():
    """A (D, H, W) volume must not silently cluster on W-dim feature
    rows through the channels-last pixel route."""
    eng = FCMServeEngine(CFG)
    with pytest.raises(ValueError, match="channels-last"):
        eng.submit(np.zeros((16, 64, 64)), method="pixel")  # volume-shaped
    with pytest.raises(ValueError):
        eng.submit(np.zeros((2, 3, 4, 5)), method="pixel")
    assert eng.queue_depth == 0


def test_bad_superpixel_request_rejected_at_ingest():
    eng = FCMServeEngine(CFG)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(64), method="superpixel")
    with pytest.raises(ValueError):
        eng.submit(np.zeros((2, 3, 4, 5)), method="superpixel")
    assert eng.queue_depth == 0


def test_unknown_method_rejected():
    eng = FCMServeEngine(CFG)
    with pytest.raises(ValueError):
        eng.submit(np.zeros((8, 8)), method="fuzzy")


def test_bad_spatial_request_rejected_at_ingest():
    """A rank-1 spatial payload must fail in submit(), not poison a
    whole flush() after the queues have been drained."""
    eng = FCMServeEngine(CFG)
    img, _ = phantom.phantom_slice(32, 32, seed=0)
    eng.submit(img)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(64), method="spatial")
    results = eng.flush()                    # the good request survives
    assert len(results) == 1 and results[0].method == "histogram"


def test_stats_shape():
    eng = FCMServeEngine(CFG)
    s = eng.stats()
    for k in ("requests", "cache_hits", "batches", "batched_images",
              "padded_lanes", "queue_depth", "cache_entries",
              "cache_hit_rate", "images_per_sec"):
        assert k in s
    assert s["requests"] == 0 and s["cache_hit_rate"] == 0.0


def test_bad_batch_sizes_rejected():
    with pytest.raises(ValueError):
        FCMServeEngine(CFG, batch_sizes=())
    with pytest.raises(ValueError):
        FCMServeEngine(CFG, batch_sizes=(0, 8))


# ---------------------------------------------------------------------------
# Route registry: cross-request batching for spatial/pixel, extensibility
# ---------------------------------------------------------------------------

def test_spatial_requests_batch_across_requests():
    """Same-shape FCM_S requests in one flush share ONE batched solve,
    and every request still gets its solo-fit trajectory."""
    from repro.core import solver as SV

    eng = FCMServeEngine(CFG, batch_sizes=(1, 8, 64))
    imgs = [phantom.noisy_phantom_slice(40, 48, noise=6.0 + 3 * i,
                                        impulse=0.04, seed=i)[0]
            for i in range(6)]
    results = eng.segment(imgs, method="spatial")
    s = eng.stats()
    assert s["spatial_batches"] == 1                 # one device loop
    assert s["spatial_batched_images"] == 6
    assert s["spatial_padded_lanes"] == 2            # 6 -> bucket 8
    for img, r in zip(imgs, results):
        solo = SV.solve(SV.spatial_problem(img.astype(np.float32),
                                           eng.spatial_cfg),
                        eng.spatial_cfg)
        np.testing.assert_allclose(r.centers, np.asarray(solo.centers),
                                   atol=1e-5)
        assert (r.labels == np.asarray(solo.labels)).all()
        assert r.n_iters == solo.n_iters


def test_spatial_mixed_shapes_bucket_separately():
    eng = FCMServeEngine(CFG, batch_sizes=(4,))
    a = [phantom.noisy_phantom_slice(32, 32, seed=i)[0] for i in range(2)]
    b = [phantom.noisy_phantom_slice(32, 48, seed=i)[0] for i in range(3)]
    eng.segment(a + b, method="spatial")
    s = eng.stats()
    assert s["spatial_batches"] == 2                 # one per grid shape
    assert s["spatial_batched_images"] == 5
    assert s["spatial_padded_lanes"] == 3            # 2->4 and 3->4


def test_pixel_requests_batch_across_requests():
    from repro.core import solver as SV

    eng = FCMServeEngine(CFG, batch_sizes=(4,))
    imgs = [phantom.phantom_slice(40, 44, noise=2.0 + i, seed=i)[0]
            for i in range(3)]
    results = eng.segment(imgs, method="pixel")
    s = eng.stats()
    assert s["pixel_batches"] == 1
    assert s["pixel_batched_images"] == 3 and s["pixel_padded_lanes"] == 1
    for img, r in zip(imgs, results):
        solo = SV.solve(SV.pixel_problem(
            img.ravel().astype(np.float32), CFG), CFG)
        np.testing.assert_allclose(r.centers, np.asarray(solo.centers),
                                   atol=1e-5)
        assert (r.labels == np.asarray(solo.labels).reshape(40, 44)).all()


# ---------------------------------------------------------------------------
# Device-resident route programs (single-dispatch serving pipeline)
# ---------------------------------------------------------------------------

def test_fused_program_matches_staged_route_path():
    """The single-dispatch histogram program must serve exactly what the
    staged build_problem -> solve_batched -> materialize path serves."""
    from repro.serving import fcm_engine as E

    imgs = [phantom.phantom_slice(48, 56, noise=2.0 + i, seed=i)[0]
            for i in range(5)]
    fused = FCMServeEngine(CFG, batch_sizes=(8,), cache_size=0)
    res_fused = fused.segment(imgs)
    assert fused.stats()["compiled_programs"] == 1

    # Staged comparator: same route minus the program hooks.
    base = E.ROUTES["histogram"]
    E.register_route(dataclasses.replace(base, program_key=None,
                                         make_program=None))
    try:
        staged = FCMServeEngine(CFG, batch_sizes=(8,), cache_size=0)
        res_staged = staged.segment(imgs)
        assert staged.stats()["compiled_programs"] == 0
    finally:
        E.register_route(base)
    for f, s in zip(res_fused, res_staged):
        np.testing.assert_allclose(f.centers, s.centers, atol=1e-5)
        assert f.n_iters == s.n_iters
        assert (f.labels == s.labels).all()


def test_fused_program_mixed_sizes_one_dispatch():
    """Heterogeneous payload sizes still share ONE solve via the
    histograms-only program flavor."""
    imgs = [phantom.phantom_slice(64 + 8 * i, 96, seed=i)[0]
            for i in range(4)]
    eng = FCMServeEngine(CFG, batch_sizes=(4,), cache_size=0)
    results = eng.segment(imgs)
    assert eng.stats()["batches"] == 1
    for img, r in zip(imgs, results):
        x = img.ravel().astype(np.float32)
        single = SV.solve(SV.histogram_problem(x, CFG), backend="reference")
        np.testing.assert_allclose(r.centers, np.asarray(single.centers),
                                   atol=1e-4)
        lab = F.labels_from_centers(jnp.asarray(x), single.centers)
        assert (r.labels == np.asarray(lab).reshape(img.shape)).all()


def test_fused_spatial_program_matches_staged_route_path():
    """The spatial route now compiles a fused stencil program (whole
    batched convergence in one launch); it must serve exactly what the
    staged build_problem -> solve_batched -> materialize path serves."""
    from repro.serving import fcm_engine as E

    imgs = [phantom.phantom_slice(40, 48, noise=2.0 + i, seed=i)[0]
            for i in range(3)]
    fused = FCMServeEngine(CFG, batch_sizes=(4,), cache_size=0,
                           trace_ring=8)
    res_fused = fused.segment(imgs, method="spatial")
    assert fused.stats()["compiled_programs"] == 1
    buckets = [c for t in fused.tracer.traces() if t["name"] == "flush"
               for c in t["children"] if c["name"] == "bucket"
               and c["attrs"]["route"] == "spatial"]
    assert buckets and buckets[-1]["attrs"]["fused"] is True
    assert [c["name"] for c in buckets[-1]["children"]] == [
        "gather", "launch", "scatter"]

    base = E.ROUTES["spatial"]
    E.register_route(dataclasses.replace(base, program_key=None,
                                         make_program=None))
    try:
        staged = FCMServeEngine(CFG, batch_sizes=(4,), cache_size=0)
        res_staged = staged.segment(imgs, method="spatial")
        assert staged.stats()["compiled_programs"] == 0
    finally:
        E.register_route(base)
    for f, s in zip(res_fused, res_staged):
        np.testing.assert_allclose(f.centers, s.centers, atol=1e-5)
        assert f.n_iters == s.n_iters
        assert (f.labels == s.labels).all()


def test_program_cache_reused_across_flushes_and_engines():
    imgs = [phantom.phantom_slice(32, 32, noise=2.0 + i, seed=i)[0]
            for i in range(3)]
    eng = FCMServeEngine(CFG, batch_sizes=(4,), cache_size=0)
    eng.segment(imgs)
    eng.segment(imgs)
    assert eng.stats()["compiled_programs"] == 1      # same shape key
    eng.segment([phantom.phantom_slice(16, 16, seed=9)[0]])
    assert eng.stats()["compiled_programs"] == 2      # new payload size


def test_program_cache_evicts_on_route_reregistration():
    """Regression: register_route replacing a spec must not leave an
    engine serving the old spec's compiled program."""
    from repro.serving import fcm_engine as E

    img, _ = phantom.phantom_slice(32, 32, seed=3)
    eng = FCMServeEngine(CFG, batch_sizes=(1,), cache_size=0)
    first = eng.segment([img])[0]
    assert eng.stats()["compiled_programs"] == 1

    base = E.ROUTES["histogram"]
    calls = []

    def make_program(e, key, bucket):
        calls.append(key)
        return base.make_program(e, key, bucket)

    E.register_route(dataclasses.replace(base, make_program=make_program))
    try:
        again = eng.segment([img])[0]
        assert calls, "stale compiled program served after re-registration"
        np.testing.assert_allclose(again.centers, first.centers, atol=1e-6)
        assert (again.labels == first.labels).all()
        # the old generation's entry was purged, not orphaned
        assert eng.stats()["compiled_programs"] == 1
    finally:
        E.register_route(base)


def test_stats_report_the_kernels_each_route_resolved():
    """route_impls names what every compiled program launches, so a
    deployment can tell the device path from a reference fallback."""
    from repro.kernels import ops as kops

    img, _ = phantom.phantom_slice(32, 32, seed=4)
    eng = FCMServeEngine(CFG, batch_sizes=(1,), cache_size=0)
    assert eng.stats()["route_impls"] == {}
    eng.segment([img])
    eng.segment([img], method="pixel")
    eng.segment([img], method="spatial")
    flat = kops.select_step("flat", batched=True, n_rows=img.size, c=4)
    assert eng.stats()["route_impls"] == {
        "histogram": [f"flat/{flat.name}"],
        "pixel": [f"flat/{flat.name}",
                  f"labels/{kops.select_step('labels').name}"],
        "spatial": [f"stencil/{kops.select_step('stencil').name}"]}


def test_stage_seconds_breakdown_in_stats():
    eng = FCMServeEngine(CFG)
    s = eng.stats()["stage_seconds"]
    assert set(s) == set(eng.stats()["method_requests"])
    for route_stages in s.values():
        assert set(route_stages) == {"ingest", "gather", "solve",
                                     "materialize"}
    img, _ = phantom.phantom_slice(32, 32, seed=0)
    eng.segment([img])
    eng.segment([img], method="spatial")
    s = eng.stats()["stage_seconds"]
    assert s["histogram"]["ingest"] >= 0 and s["histogram"]["solve"] > 0
    assert s["histogram"]["gather"] > 0 and s["spatial"]["gather"] > 0
    assert s["spatial"]["solve"] > 0


def test_histogram_materialize_lut_matches_labels_from_centers():
    """Satellite: the np defuzzify LUT used for cache hits / duplicates
    is numerically identical to the old jnp labels_from_centers path."""
    import jax.numpy as jnp
    from repro.serving.fcm_engine import _label_lut

    rng = np.random.default_rng(0)
    for _ in range(5):
        centers = np.sort(rng.uniform(0, 255, 4)).astype(np.float32)
        vals = jnp.arange(256, dtype=jnp.float32)
        want = np.asarray(F.labels_from_centers(vals, jnp.asarray(centers)))
        np.testing.assert_array_equal(_label_lut(centers, 256), want)
    # exact ties resolve to the lowest cluster index in both
    centers = np.asarray([10.0, 30.0, 20.0], np.float32)
    vals = jnp.arange(256, dtype=jnp.float32)
    np.testing.assert_array_equal(
        _label_lut(centers, 256),
        np.asarray(F.labels_from_centers(vals, jnp.asarray(centers))))


def test_pixel_materialize_fused_labels_match_full_membership_path():
    """Satellite: pixel-route labels via the fused argmin kernel path
    equal the old materialize-the-membership-then-argmax path."""
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    rng = np.random.default_rng(1)
    x = rng.uniform(0, 255, 4000).astype(np.float32)
    v = np.sort(rng.uniform(10, 240, 4)).astype(np.float32)
    old = np.asarray(F.defuzzify(F.update_membership(
        jnp.asarray(x), jnp.asarray(v), 2.0)))
    new = np.asarray(kops.defuzzify_labels(jnp.asarray(x), jnp.asarray(v)))
    np.testing.assert_array_equal(new, old)
    # and through the kernel itself (interpret mode)
    kern = np.asarray(kops.defuzzify_labels_batched(
        jnp.asarray(x)[None], jnp.asarray(v)[None],
        impl="pallas", interpret=True))[0]
    np.testing.assert_array_equal(kern, old)


def test_uint8_zero_copy_ingest_matches_clipped_path():
    """uint8 payloads skip the clip pass; results must match a clipped
    int submission of the same values."""
    img_u8 = phantom.phantom_slice(40, 40, seed=7)[0]
    assert img_u8.dtype == np.uint8
    eng = FCMServeEngine(CFG, cache_size=0)
    a = eng.segment([img_u8])[0]
    b = eng.segment([img_u8.astype(np.int32)])[0]
    np.testing.assert_allclose(a.centers, b.centers, atol=0)
    assert (a.labels == b.labels).all()


def test_route_registration_roundtrip():
    """A new serving method costs one RouteSpec registration: flush,
    bucketing and stats need no engine changes."""
    from repro.serving import fcm_engine as E

    base = E.ROUTES["histogram"]
    spec = E.RouteSpec(name="histogram-shadow", ingest=base.ingest,
                       bucket_key=base.bucket_key,
                       build_problem=base.build_problem,
                       materialize=base.materialize,
                       cacheable=False, stats_prefix="histogram_shadow")
    E.register_route(spec)
    try:
        assert "histogram-shadow" in E.METHODS
        eng = FCMServeEngine(CFG)
        img, _ = phantom.phantom_slice(32, 32, seed=0)
        res = eng.segment([img], method="histogram-shadow")[0]
        direct = eng.segment([img])[0]
        np.testing.assert_allclose(res.centers, direct.centers, atol=1e-5)
        s = eng.stats()
        assert s["histogram_shadow_batches"] == 1
        assert s["method_requests"]["histogram-shadow"] == 1
    finally:
        del E.ROUTES["histogram-shadow"]
        E.METHODS = tuple(E.ROUTES)


# ---------------------------------------------------------------------------
# Observability layer (PR 6)
# ---------------------------------------------------------------------------

def test_stats_latency_percentiles_per_route(volume):
    eng = FCMServeEngine(CFG, batch_sizes=(4, 16), cache_size=0)
    eng.segment(volume)
    lat = eng.stats()["latency"]["histogram"]
    assert lat["count"] == len(volume)       # one sample per request
    for k in ("p50", "p90", "p99", "mean", "min", "max"):
        assert lat[k] is not None and lat[k] > 0.0
    assert lat["min"] <= lat["p50"] <= lat["p99"] <= lat["max"]
    # untouched routes keep an empty (schema'd) histogram
    assert eng.stats()["latency"]["spatial"]["count"] == 0


def test_stats_convergence_per_route(volume):
    eng = FCMServeEngine(CFG, batch_sizes=(4, 16), cache_size=0)
    results = eng.segment(volume)
    conv = eng.stats()["convergence"]["histogram"]
    iters = [r.n_iters for r in results]
    assert conv["lanes"] == len(volume)
    assert conv["mean_iters"] == pytest.approx(np.mean(iters), abs=1e-6)
    assert conv["p50_iters"] == pytest.approx(np.percentile(iters, 50),
                                              abs=1.0)
    # the residual is the center-movement delta at the final accepted
    # iteration (convergence itself gates on membership change)
    assert conv["last_final_delta"] is not None
    assert np.isfinite(conv["last_final_delta"])
    assert conv["last_final_delta"] >= 0.0
    # a route that never solved reports no residual
    assert eng.stats()["convergence"]["pixel"]["last_final_delta"] is None


def test_cache_hits_do_not_pollute_convergence(volume):
    eng = FCMServeEngine(CFG, batch_sizes=(1, 8))
    eng.segment([volume[0]])
    eng.segment([volume[0]])                 # cache hit: no solve ran
    conv = eng.stats()["convergence"]["histogram"]
    assert conv["lanes"] == 1
    lat = eng.stats()["latency"]["histogram"]
    assert lat["count"] == 2                 # but both requests have latency


def test_reset_stats_zeroes_but_keeps_schema(volume):
    eng = FCMServeEngine(CFG, batch_sizes=(4, 16), cache_size=0)
    eng.segment(volume)
    before = eng.stats()
    assert before["requests"] == len(volume)
    eng.reset_stats()
    after = eng.stats()
    assert set(after) == set(before)         # same schema
    assert after["requests"] == 0 and after["batches"] == 0
    assert after["latency"]["histogram"]["count"] == 0
    assert after["convergence"]["histogram"]["lanes"] == 0
    assert eng.tracer.traces() == []
    # and the engine keeps serving after a reset
    res = eng.segment([volume[0]])[0]
    assert res.labels.shape == volume[0].shape
    assert eng.stats()["requests"] == 1


def test_snapshot_is_plain_json(volume):
    import json as _json
    eng = FCMServeEngine(CFG, batch_sizes=(4, 16))
    eng.segment(volume)
    eng.segment([volume[0]], method="spatial")
    snap = eng.snapshot()
    _json.dumps(snap)                        # no numpy scalars anywhere
    assert set(snap) == {"stats", "metrics", "traces"}
    assert snap["stats"]["requests"] == len(volume) + 1
    assert "route.latency_seconds{route=histogram}" in \
        snap["metrics"]["histograms"]


def test_trace_ring_records_flush_tree(volume):
    eng = FCMServeEngine(CFG, batch_sizes=(4, 16), cache_size=0,
                         trace_ring=8)
    eng.segment(volume[:4])
    flushes = [t for t in eng.tracer.traces() if t["name"] == "flush"]
    assert flushes
    bucket = flushes[-1]["children"][0]
    assert bucket["name"] == "bucket"
    assert bucket["attrs"]["route"] == "histogram"
    assert bucket["attrs"]["n"] == 4
    stages = [c["name"] for c in bucket["children"]]
    assert "scatter" in stages or "solve" in stages
    launch = [c for c in bucket["children"]
              if c["name"] in ("launch", "solve")][0]
    assert launch.get("device_s") is not None  # fenced device time


def test_tracing_disabled_keeps_stats_but_no_traces(volume):
    eng = FCMServeEngine(CFG, batch_sizes=(4, 16), cache_size=0,
                         tracing=False)
    eng.segment(volume)
    s = eng.stats()
    assert s["requests"] == len(volume)
    assert s["latency"]["histogram"]["count"] == len(volume)
    assert s["stage_seconds"]["histogram"]["solve"] > 0
    assert eng.tracer.traces() == []


def test_compress_seconds_accounted_per_route():
    """Satellite: compress used to land in one global stats key; it is
    now a per-route stage counter surfaced through route.stat()."""
    rgb = np.stack([phantom.phantom_slice(48, 48, seed=i)[0]
                    for i in range(3)], axis=-1)
    eng = FCMServeEngine(CFG)
    eng.segment([rgb], method="superpixel")
    s = eng.stats()
    assert s["superpixel_compress_seconds"] > 0.0
    assert s["compress_seconds"] == pytest.approx(
        s["superpixel_compress_seconds"])
    # histogram traffic adds no compress time
    img, _ = phantom.phantom_slice(32, 32, seed=0)
    eng.segment([img])
    assert eng.stats()["compress_seconds"] == pytest.approx(
        eng.stats()["superpixel_compress_seconds"])
