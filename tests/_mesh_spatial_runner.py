"""Subprocess entry for the four-chip spatial deployment's test: on 4
fake host devices, an engine whose spatial launches shard over a
``data`` mesh serves noisy uint8 phantom slices at buckets 1, 4, 8, 16
and 64. Its answers must agree with a plain float32 FCM_S written here,
be bitwise those of a one-device engine, labels uint8 from both, and
its shard counters must match a hand count of the lanes each shard ran.
Prints MESH_SPATIAL_OK on success."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
sys.path.insert(0, _SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import fcm as F  # noqa: E402
from repro.core.spatial import SpatialFCMConfig  # noqa: E402
from repro.data import phantom  # noqa: E402
from repro.obs import tracing  # noqa: E402
from repro.serving.fcm_engine import FCMServeEngine  # noqa: E402

H, W = 40, 32
C, M, ALPHA, EPS, MAX_ITERS = 4, 2.0, 1.0, 5e-3, 300
N8 = ((-1, 0), (1, 0), (0, -1), (0, 1), (-1, -1), (-1, 1), (1, -1), (1, 1))
GAP_MIN = 1e-4


# -- the plain reference: FCM_S (Ahmed et al. 2002), 8 neighbours --------

def _shift(a, dy, dx):
    """out[y, x] = a[y - dy, x - dx], zero outside."""
    p = jnp.pad(a, ((max(dy, 0), max(-dy, 0)), (max(dx, 0), max(-dx, 0))))
    return p[max(-dy, 0):max(-dy, 0) + a.shape[0],
             max(-dx, 0):max(-dx, 0) + a.shape[1]]


@jax.jit
def _effective(img, v):
    """(effective distances (c, H, W), neighbour mean xbar (H, W)):
    the squared distance plus ``ALPHA`` times the mean squared distance
    of the in-image neighbours."""
    cnt = jnp.zeros_like(img)
    sx = jnp.zeros_like(img)
    nb = jnp.zeros((C,) + img.shape, jnp.float32)
    for dy, dx in N8:
        xs, ws = _shift(img, dy, dx), _shift(jnp.ones_like(img), dy, dx)
        cnt, sx = cnt + ws, sx + ws * xs
        nb = nb + ws[None] * (v[:, None, None] - xs[None]) ** 2
    cnt = jnp.maximum(cnt, 1.0)
    d2 = (v[:, None, None] - img[None]) ** 2
    return d2 + ALPHA * nb / cnt[None], sx / cnt


@jax.jit
def _step(img, v):
    dist, xbar = _effective(img, v)
    p = jnp.maximum(dist, 1e-12) ** (-1.0 / (M - 1.0))
    um = (p / p.sum(axis=0)) ** M
    x_eff = (img + ALPHA * xbar) / (1.0 + ALPHA)
    return (um * x_eff[None]).sum(axis=(1, 2)) / um.sum(axis=(1, 2))


def reference(img):
    """(centers, iterations, tolerance): centers start evenly inside the
    intensity range; the fit stops after the first step that moves no
    center by ``tol = EPS * range * 0.1`` or more."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(img, jnp.float32)
        lo, hi = float(x.min()), float(x.max())
        v = jnp.asarray(lo + (np.arange(C) + 0.5) / C * (hi - lo),
                        jnp.float32)
        tol = EPS * (hi - lo) * 0.1
        for it in range(1, MAX_ITERS + 1):
            v_new = _step(x, v)
            moved = float(jnp.max(jnp.abs(v_new - v)))
            v = v_new
            if moved < tol:
                break
    return np.asarray(v), it, tol


def check_against_reference(img, r):
    v_ref, it_ref, tol = reference(img)
    dev = np.abs(np.asarray(r.centers) - v_ref).max() / tol
    assert dev <= 1.0, (dev, r.centers, v_ref)
    assert abs(r.n_iters - it_ref) <= 1, (r.n_iters, it_ref)
    # Labels of the served centers, on pixels clear of a near tie.
    with jax.default_matmul_precision("highest"):
        dist = np.asarray(_effective(jnp.asarray(img, jnp.float32),
                                     jnp.asarray(r.centers, jnp.float32))[0])
    own = np.argmin(dist, axis=0)
    least = np.sort(dist, axis=0)
    clear = (least[1] - least[0]) > GAP_MIN * least[1]
    assert clear.mean() > 0.9, clear.mean()
    assert (r.labels == own)[clear].all(), \
        int((r.labels != own)[clear].sum())


# -- the engines --------------------------------------------------------

class _Annotations:
    """Records the ``fcm.bucket`` annotations' attributes."""

    def __init__(self):
        self.seen = []
        self._real = tracing.TraceAnnotation

    def __call__(self, name, **kw):
        if name == "fcm.bucket":
            self.seen.append(kw)
        return self._real(name, **kw)


def engine(mesh=None):
    cfg = SpatialFCMConfig(n_clusters=C, m=M, eps=EPS, max_iters=MAX_ITERS,
                           alpha=ALPHA, neighbors=8)
    return FCMServeEngine(F.FCMConfig(n_clusters=C, max_iters=MAX_ITERS),
                          batch_sizes=(1, 4, 8, 16, 64), spatial_cfg=cfg,
                          cache_size=0, mesh=mesh)


def counters(eng):
    return [eng.metrics.counter(f"route.{k}", route="spatial").value
            for k in ("sharded_batches", "shard_iters_sum",
                      "shard_iters_max")]


def main():
    assert len(jax.devices()) == 4, jax.devices()
    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    eng, one = engine(mesh), engine()
    notes = _Annotations()
    tracing.TraceAnnotation = notes
    slices = [phantom.noisy_phantom_slice(H, W, slice_pos=0.3 + 0.05 * i,
                                          noise=15.0, impulse=0.05,
                                          seed=70 + i)[0] for i in range(8)]
    # One slow lane: uniform noise has no clusters to settle into and
    # takes about half as many iterations again as a phantom slice.
    slow = np.random.default_rng(3).integers(0, 256, (H, W)).astype(np.uint8)
    more = [phantom.noisy_phantom_slice(H, W, slice_pos=0.3 + 0.01 * i,
                                        noise=15.0, impulse=0.05,
                                        seed=100 + i)[0] for i in range(40)]
    batches = [slices[:1], slices[1:4], slices[:5] + [slow] + slices[5:7],
               more[:12], more]
    for batch, bucket in zip(batches, (1, 4, 8, 16, 64)):
        before = counters(eng)
        notes.seen.clear()
        got = eng.segment(batch, method="spatial")
        delta = [a - b for a, b in zip(counters(eng), before)]
        seen = [(a["bucket"], a["shards"]) for a in notes.seen]
        solo = one.segment(batch, method="spatial")
        for img, r, s in zip(batch, got, solo):
            check_against_reference(img, r)
            assert np.array_equal(np.asarray(r.centers),
                                  np.asarray(s.centers)), bucket
            assert r.n_iters == s.n_iters, bucket
            assert np.array_equal(r.labels, s.labels), bucket
            assert r.labels.dtype == s.labels.dtype == np.uint8, bucket
        shards = 1 if bucket == 1 else 4
        assert seen == [(bucket, shards)], seen
        if bucket == 1:
            assert delta == [0, 0, 0], delta
            continue
        # Hand count: padding lanes replay lane 0 and run as it does.
        iters = [r.n_iters for r in got]
        iters += [iters[0]] * (bucket - len(iters))
        per = np.asarray(iters).reshape(4, -1).sum(axis=1)
        assert delta == [1, per.sum(), 4 * per.max()], (delta, per)
        if bucket == 8:
            assert got[5].n_iters > max(r.n_iters for r in got[:5]), iters
            assert delta[1] < delta[2], delta
    assert counters(eng)[0] == 4
    assert counters(one) == [0, 0, 0]
    print("MESH_SPATIAL_OK")


if __name__ == "__main__":
    main()
