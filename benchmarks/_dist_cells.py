"""Subprocess entry for the sweep's distributed family: measures the
shard_map solver cells under 8 fake host devices (the flag must be set
before jax initialises, hence a fresh process) and prints one JSON
document to stdout.

Three modes (``bench_schema.REQUIRED_DIST_MODES``):

* ``batch_hist``  — ragged histogram batch, batch axis sharded via
  ``batched.fit_batched_sharded``; parity vs the unsharded
  ``solve_batched`` must be exact on per-lane iteration counts (the
  active-lane mask keeps padding lanes out of the convergence scalar).
* ``pixel_flat``  — one image, pixel axis sharded via
  ``distributed.fit_sharded``; parity vs the reference solve.
* ``pixel_hist``  — same, through the histogram-compressed path.

Run:  PYTHONPATH=src python benchmarks/_dist_cells.py [--tiny]
"""
import json
import os
import sys
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
sys.path.insert(0, _SRC)

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.core import fcm as F  # noqa: E402
from repro.core import batched as B  # noqa: E402
from repro.core import solver as SV  # noqa: E402
from repro.core import distributed as D  # noqa: E402
from repro.data import phantom  # noqa: E402


def _best_of(fn, reps):
    fn()                                        # warm compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main(argv=None):
    tiny = "--tiny" in (argv or sys.argv[1:])
    n_dev = len(jax.devices())
    assert n_dev == 8, jax.devices()
    mesh = jax.make_mesh((n_dev,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    cfg = F.FCMConfig(max_iters=300)
    reps = 1 if tiny else 3
    size = 64 if tiny else 128
    batch = 6 if tiny else 10
    cells = []

    # -- batch_hist: ragged batch, batch axis sharded -------------------
    imgs = [phantom.phantom_slice(size + 8 * (z % 3), size,
                                  slice_pos=0.3 + 0.04 * z, seed=z)[0]
            for z in range(batch)]
    hists = B.histograms_of(imgs)
    shard = B.fit_batched_sharded(hists, mesh, cfg)
    problem = SV.batch_problems(B.hist_rows(hists), hists, cfg=cfg)
    local = SV.solve_batched(problem, backend="reference")
    wall = _best_of(lambda: B.fit_batched_sharded(hists, mesh, cfg), reps)
    max_dc = float(np.max(np.abs(np.asarray(shard.centers)
                                 - np.asarray(local.centers))))
    iters_eq = bool(np.array_equal(np.asarray(shard.n_iters),
                                   np.asarray(local.n_iters)))
    cells.append({
        "mode": "batch_hist", "batch": batch,
        "wall_s": wall, "per_image_s": wall / batch,
        "parity": {"ok": max_dc < 1e-4 and iters_eq,
                   "max_center_delta": max_dc,
                   "n_iters_equal": iters_eq},
    })

    # -- pixel_flat / pixel_hist: one image, pixel axis sharded ---------
    img, _ = phantom.phantom_slice(size, size, seed=11)
    x = img.ravel().astype(np.float32)
    ref = SV.solve(SV.pixel_problem(x, cfg), backend="reference")
    for mode, histogram in (("pixel_flat", False), ("pixel_hist", True)):
        res = D.fit_sharded(x, mesh, cfg, histogram=histogram)
        wall = _best_of(
            lambda h=histogram: D.fit_sharded(x, mesh, cfg, histogram=h),
            reps)
        max_dc = float(np.max(np.abs(
            np.sort(np.asarray(res.centers))
            - np.sort(np.asarray(ref.centers)))))
        agree = float((np.asarray(res.labels)
                       == np.asarray(ref.labels)).mean())
        cells.append({
            "mode": mode, "batch": 1,
            "wall_s": wall, "per_image_s": wall,
            "parity": {"ok": max_dc < 0.75 and agree > 0.995,
                       "max_center_delta": max_dc,
                       "label_agreement": agree},
        })

    print(json.dumps({"devices": n_dev, "tiny": tiny, "cells": cells}))


if __name__ == "__main__":
    main()
