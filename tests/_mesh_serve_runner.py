"""Subprocess entry for mesh-serving tests: an FCMServeEngine with its
RouteProgram launches sharded over 8 fake host devices must serve
results identical to the single-device engine — through both the sync
and async front doors, and through the reference programs a held-open
breaker degrades to — and set_mesh must never serve a stale program.
Prints MESH_SERVE_OK on success."""
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
from repro.data import phantom  # noqa: E402
from repro.serving.fcm_engine import FCMServeEngine  # noqa: E402


def _check_same(a, b):
    assert (a.labels == b.labels).all()
    np.testing.assert_array_equal(a.centers, b.centers)
    assert a.n_iters == b.n_iters


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    cfg = F.FCMConfig(max_iters=300)
    # bucket 8 divides the mesh; bucket 1 exercises the single-device
    # fallback inside a meshed engine (mesh does not divide the bucket).
    imgs = [phantom.phantom_slice(32, 32, noise=4.0 + (i % 3),
                                  seed=500 + i)[0] for i in range(11)]

    single = FCMServeEngine(cfg, batch_sizes=(1, 8), cache_size=0)
    meshed = FCMServeEngine(cfg, batch_sizes=(1, 8), cache_size=0,
                            mesh=mesh, max_wait_ms=10_000.0)

    # Sync parity: same buckets, mesh-sharded vs single-device launch.
    ref = single.segment(imgs)
    got = meshed.segment(imgs)
    for a, b in zip(got, ref):
        _check_same(a, b)

    # Async parity through the mesh: futures resolve with the same
    # results the single-device sync path produced.
    futs = [meshed.submit_async(im) for im in imgs]
    meshed.drain()
    for f, b in zip(futs, ref):
        _check_same(f.result(timeout=30), b)

    # A held-open breaker sends every chunk to the reference program,
    # sharded over the mesh as the fast one is: the same answers.
    degraded = FCMServeEngine(cfg, batch_sizes=(1, 8), cache_size=0,
                              mesh=mesh, breaker_cooldown_s=1e9)
    with degraded._lock:
        br = degraded._breaker("histogram")
        br["opened_t"] = time.perf_counter()
        degraded._set_breaker("histogram", br, "open")
    for a, b in zip(degraded.segment(imgs), ref):
        _check_same(a, b)
    assert degraded.stats()["compiled_programs"] == 0
    # 11 requests: buckets of 8 and 3 (padded to 8), both sharded.
    assert degraded.metrics.counter("route.sharded_batches",
                                    route="histogram").value == 2
    degraded.shutdown()

    # set_mesh(None) detaches: programs recompile (new generation) and
    # keep serving identical results.
    meshed.set_mesh(None)
    for a, b in zip(meshed.segment(imgs), ref):
        _check_same(a, b)

    # A one-device mesh is the degenerate single-device path.
    one = jax.make_mesh((1,), ("data",),
                        axis_types=(jax.sharding.AxisType.Auto,))
    meshed.set_mesh(one)
    for a, b in zip(meshed.segment(imgs), ref):
        _check_same(a, b)

    single.shutdown()
    meshed.shutdown()
    print("MESH_SERVE_OK")


if __name__ == "__main__":
    main()
