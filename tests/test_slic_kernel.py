"""Interpret-mode parity of the SLIC Pallas assignment kernel against
the pure-jnp reference, on tile-aligned, non-128-multiple, and
border-heavy shapes (CI runs this file in the kernel-parity lane)."""
import numpy as np
import pytest

from repro.data import phantom
from repro.kernels import ops as kops
from repro.superpixel import slic as SL

# (H, W, n_segments): aligned, ragged both axes, width-dominant strip
# (every pixel row borders padding), and a tall sliver.
SHAPES = [(64, 128, 48), (37, 61, 12), (16, 300, 30), (129, 131, 100),
          (200, 40, 20)]


def _img(h, w, channels, seed=0):
    if channels == 1:
        return phantom.phantom_slice(h, w, seed=seed)[0].astype(np.float32)
    img, _ = phantom.phantom_slice_rgb(h, w, seed=seed)
    return img.astype(np.float32)[:, :, :channels]


def _assign_both(img, centers, gy, gx, sw):
    """One assignment step through the reference and the kernel."""
    h, w = img.shape[:2]
    ref = np.asarray(SL.assign_ref(img, centers, gy, gx, sw))
    xpad, _ = kops.tile_channels(img)
    ker = np.asarray(kops.slic_assign(xpad, centers, h, w, gy, gx, sw,
                                      interpret=True))[:h, :w]
    return ref, ker


def _joint_d2(img, centers, sw, ys, xs, ks):
    """Joint distance of pixels (ys, xs) to centers ks, in float32 and
    in the order both implementations accumulate it."""
    d = img.shape[2]
    c = np.asarray(centers, np.float32)[ks]
    px = img[ys, xs]
    d2 = np.zeros(len(ys), np.float32)
    for ch in range(d):
        d2 = d2 + (px[:, ch] - c[:, ch]) ** 2
    d2 = d2 + np.float32(sw) * (ys.astype(np.float32) - c[:, d]) ** 2
    d2 = d2 + np.float32(sw) * (xs.astype(np.float32) - c[:, d + 1]) ** 2
    return d2


@pytest.mark.parametrize("h,w,segs", SHAPES)
@pytest.mark.parametrize("channels", [1, 3])
def test_assignment_step_parity(h, w, segs, channels):
    """A single assignment step agrees: same candidate sets, same
    accumulation order, same lowest-index tie resolution. The two are
    separate XLA programs, and the CPU compiler may round one of them
    differently (fused multiply-adds), so a pixel whose two best
    candidates lie within a few ulp of each other may go either way.
    Every disagreeing pixel must be such a near-tie; any other
    disagreement is a real assignment bug."""
    img = _img(h, w, channels, seed=h + w + channels)
    gy, gx = SL.grid_shape(h, w, segs)
    sw = SL.spatial_weight(h, w, gy, gx, 10.0)
    centers = SL.seed_centers(img, gy, gx)
    ref, ker = _assign_both(img, centers, gy, gx, sw)
    assert ref.shape == ker.shape == (h, w)
    ys, xs = np.nonzero(ref != ker)
    hwd = img if img.ndim == 3 else img[:, :, None]
    d_ref = _joint_d2(hwd, centers, sw, ys, xs, ref[ys, xs])
    d_ker = _joint_d2(hwd, centers, sw, ys, xs, ker[ys, xs])
    ulp = np.spacing(np.maximum(d_ref, d_ker))
    assert (np.abs(d_ref - d_ker) <= 4 * ulp).all(), \
        list(zip(ys, xs, d_ref, d_ker))


@pytest.mark.parametrize("h,w,segs", SHAPES)
def test_assignment_parity_after_center_drift(h, w, segs):
    """Parity must also hold off the seed grid: run a few reference
    iterations so centers sit at irregular positions, then compare."""
    img = _img(h, w, 3, seed=1)
    gy, gx = SL.grid_shape(h, w, segs)
    sw = SL.spatial_weight(h, w, gy, gx, 10.0)
    centers = SL.seed_centers(img, gy, gx)
    for _ in range(3):
        labels = SL.assign_ref(img, centers, gy, gx, sw)
        centers, _ = SL.update_centers(img, labels, centers)
    ref, ker = _assign_both(img, centers, gy, gx, sw)
    agree = float((ref == ker).mean())
    assert agree >= 0.999, agree


@pytest.mark.parametrize("h,w,segs", SHAPES[:3])
def test_full_fit_parity_and_broadcast(h, w, segs):
    """End-to-end fit_slic: label maps agree on >= 99.9% of pixels and
    a label broadcast through the two maps is byte-identical."""
    img = _img(h, w, 3, seed=2)
    params = SL.SLICParams(n_segments=segs)
    r_ref = SL.fit_slic(img, params)
    r_ker = SL.fit_slic(img, params, use_pallas=True, interpret=True)
    lab_ref = np.asarray(r_ref.labels)
    lab_ker = np.asarray(r_ker.labels)
    assert lab_ref.shape == lab_ker.shape == (h, w)
    assert lab_ker.dtype == np.int32
    agree = float((lab_ref == lab_ker).mean())
    assert agree >= 0.999, agree
    # Byte-identical broadcast: any per-superpixel coloring gathered
    # through the two maps must match wherever the maps agree (and the
    # maps themselves are byte-identical when agreement is exact).
    k = r_ref.centers.shape[0]
    coloring = np.arange(k, dtype=np.int32) % 7
    b_ref, b_ker = coloring[lab_ref], coloring[lab_ker]
    if agree == 1.0:
        assert b_ref.tobytes() == b_ker.tobytes()
    else:
        assert (b_ref == b_ker).mean() >= 0.999


def test_labels_cover_every_nonempty_superpixel():
    img = _img(96, 96, 3)
    res = SL.fit_slic(img, SL.SLICParams(n_segments=64), use_pallas=True,
                      interpret=True)
    lab = np.asarray(res.labels)
    counts = np.asarray(res.counts)
    assert lab.min() >= 0 and lab.max() < res.gy * res.gx
    # counts from the validity-weighted update match the label map
    np.testing.assert_allclose(
        np.bincount(lab.ravel(), minlength=res.gy * res.gx), counts)
    assert counts.sum() == img.shape[0] * img.shape[1]


def test_auto_block_rows_respects_vmem_budget():
    from repro.kernels.slic_assign import LANES, auto_block_rows

    for k, w in [(64, 96), (256, 512), (256, 2048), (1024, 4096)]:
        rows = auto_block_rows(k, w)
        kp = k + (-k) % LANES
        wp = w + (-w) % LANES
        assert 1 <= rows <= 64
        # either within the 4 MB budget, or already at the floor of 1
        assert kp * rows * wp * 4 <= 4 * 1024 * 1024 or rows == 1
        if rows >= 8:
            assert rows % 8 == 0
    # small problems get deep blocks, wide ones get shallow blocks
    assert auto_block_rows(64, 96) == 64
    assert auto_block_rows(256, 2048) < 8


def test_parity_with_auto_block_rows():
    """fit_slic's auto-sized row blocks (here 64, not the old 8) must
    not change the labels: the grid split is invisible to the argmin."""
    img = _img(70, 90, 3, seed=9)
    params = SL.SLICParams(n_segments=24)
    r_auto = SL.fit_slic(img, params, use_pallas=True, interpret=True)
    r_8 = SL.fit_slic(img, params, use_pallas=True, block_rows=8,
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(r_auto.labels),
                                  np.asarray(r_8.labels))


def test_padded_pixels_do_not_leak_into_centers():
    """A width that pads by 67 lanes: center feature means must stay
    inside the true data range (padding rows carry weight 0)."""
    img = np.full((24, 61), 200.0, np.float32)
    res = SL.fit_slic(img, SL.SLICParams(n_segments=6), use_pallas=True,
                      interpret=True)
    feats = np.asarray(res.centers[:, 0])
    counts = np.asarray(res.counts)
    np.testing.assert_allclose(feats[counts > 0], 200.0, atol=1e-4)
