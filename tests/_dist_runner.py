"""Subprocess entry for multi-device tests: runs under 8 fake host
devices (set here, NOT globally — see dry-run rule in the launcher)."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
sys.path.insert(0, _SRC)

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.core import fcm as F  # noqa: E402
from repro.core import batched as B  # noqa: E402
from repro.core import solver as SV  # noqa: E402
from repro.core import distributed as D  # noqa: E402
from repro.data import phantom  # noqa: E402


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    img, _ = phantom.phantom_slice(256, 256, seed=11)
    x = img.ravel().astype(np.float32)

    single = SV.solve(SV.pixel_problem(x), backend="reference",
                      max_iters=300)
    sharded = D.fit_sharded(x, mesh, F.FCMConfig(max_iters=300))
    np.testing.assert_allclose(np.sort(np.asarray(single.centers)),
                               np.sort(np.asarray(sharded.centers)),
                               atol=0.75)
    agree = (np.asarray(single.labels) == np.asarray(sharded.labels)).mean()
    assert agree > 0.995, agree

    hist = D.fit_sharded(x, mesh, F.FCMConfig(max_iters=300), histogram=True)
    np.testing.assert_allclose(np.sort(np.asarray(sharded.centers)),
                               np.sort(np.asarray(hist.centers)), atol=0.75)

    # Odd N exercising the padding path.
    x_odd = x[:50021]
    s2 = D.fit_sharded(x_odd, mesh, F.FCMConfig(max_iters=300))
    f2 = SV.solve(SV.pixel_problem(x_odd), backend="reference",
                  max_iters=300)
    np.testing.assert_allclose(np.sort(np.asarray(s2.centers)),
                               np.sort(np.asarray(f2.centers)), atol=0.75)
    assert s2.labels.shape[0] == 50021

    # Batched multi-image fit with the batch axis split over the mesh:
    # every lane must match the unsharded batched fit, including the
    # pad-to-mesh-size path (10 lanes on 8 devices -> 6 padding lanes).
    imgs = [phantom.phantom_slice(64 + 8 * (z % 3), 96,
                                  slice_pos=0.3 + 0.04 * z, seed=z)[0]
            for z in range(10)]
    hists = B.histograms_of(imgs)
    import warnings  # the adapter pair under test warns by design
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        local = B.fit_batched(hists, F.FCMConfig(max_iters=300))
    shard = B.fit_batched_sharded(hists, mesh, F.FCMConfig(max_iters=300))
    np.testing.assert_allclose(np.asarray(shard.centers),
                               np.asarray(local.centers), atol=1e-4)
    np.testing.assert_array_equal(shard.n_iters, local.n_iters)

    print("DIST_OK")


if __name__ == "__main__":
    main()
