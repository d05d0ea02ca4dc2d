"""Multi-pod FCM via shard_map (beyond-paper optimization #3).

The paper's two-level reduction (CUDA shared-memory block sums -> device
global partials -> single-thread combine) generalizes to the pod scale:

  VMEM tile accumulation (Pallas / XLA fusion)      <- paper's level 1
  per-device partial sums                            <- paper's level 2
  psum over the ICI/DCN mesh (2c floats/iteration)   <- paper's "stay on
                                                        device" combine,
                                                        across devices

Pixels are sharded over **every** mesh axis (clustering has no model
dimension), so the same code runs on an 8-device CPU test mesh, a 256-chip
pod, or a multi-pod (pod, data, model) mesh. Per-iteration collective
traffic is O(c) floats independent of N — the algorithm is communication-
trivial and scales to thousands of nodes; fault tolerance only needs the
c-float center vector (see repro/training/checkpoint.py notes).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import fcm as F
from . import histogram as H
from . import solver as SV

def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-axes check off: the bodies run
    ``while_loop``s whose carries mix per-device and replicated values,
    which the check rejects."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def pad_to_devices(x, n_devices: int):
    """Pad (N,)->(N', ) with N' % n_devices == 0; returns (x_pad, w_pad).

    This masks padded *pixels* of one image: zero weights drop them from
    every weighted partial sum, so they cannot shift centers or the
    convergence test. Padded batch *lanes* (whole fake images added to
    round a ragged batch up to the mesh size) are masked differently —
    via the ``active`` mask of ``solver.masked_while_centers``, which
    freezes them at iteration 0 so they can't perturb per-lane or total
    iteration counts (see ``batched.fit_batched_sharded``)."""
    n = x.shape[0]
    n_pad = (-n) % n_devices
    xp = jnp.concatenate([jnp.asarray(x, jnp.float32),
                          jnp.zeros((n_pad,), jnp.float32)])
    w = jnp.concatenate([jnp.ones((n,), jnp.float32),
                         jnp.zeros((n_pad,), jnp.float32)])
    return xp, w


def masked_center_step(x, w, v, m):
    """Fused v->v' step with a validity mask (local partial sums only).
    The numerator is a broadcast-multiply-sum, as in
    :func:`repro.core.solver.weighted_center_step`: ``um @ x`` would run
    at the TPU's default matmul precision, which rounds f32 operands
    to bf16."""
    u = F.update_membership(x, v, m)          # (c, n_local)
    um = (u ** m) * w[None, :]
    num = jnp.sum(um * x[None, :], axis=1)    # (c,)
    den = jnp.sum(um, axis=1)                 # (c,)
    return num, den


def build_sharded_fit(mesh: Mesh, cfg: F.FCMConfig = F.FCMConfig()):
    """Returns jit(fn)(x_padded, weights) -> (centers, n_iters, delta).

    The returned function is AOT-lowerable with ShapeDtypeStructs (used by
    the dry-run). Pixels and weights must be pre-padded to a multiple of
    the mesh size; shard over all mesh axes on dim 0.
    """
    axes = mesh_axes(mesh)
    xspec = P(axes)           # dim0 sharded over every axis
    rspec = P()               # replicated

    c, m, max_iters = cfg.n_clusters, cfg.m, cfg.max_iters

    def local_fit(x, w):
        # --- init: global min/max via one tiny collective ---
        big = jnp.asarray(3.4e38, jnp.float32)
        lo = jax.lax.pmin(jnp.min(jnp.where(w > 0, x, big)), axes)
        hi = jax.lax.pmax(jnp.max(jnp.where(w > 0, x, -big)), axes)
        frac = (jnp.arange(c, dtype=jnp.float32) + 0.5) / c
        v0 = lo + frac * (hi - lo)
        eps_v = cfg.eps * jnp.maximum(hi - lo, 1.0) * 0.1

        def step(v):
            num, den = masked_center_step(x, w, v, m)
            num = jax.lax.psum(num, axes)          # 2c floats on the wire
            den = jax.lax.psum(den, axes)
            return num / jnp.maximum(den, 1e-12)

        # The convergence test is the solver core's — only the step
        # (with its psums) is distributed-specific.
        v, delta, it = SV.while_centers(step, v0, eps_v, max_iters)
        labels = F.labels_from_centers(x, v)
        return v, labels, delta, it

    fn = shard_map(local_fit, mesh=mesh,
                   in_specs=(xspec, xspec),
                   out_specs=(rspec, xspec, rspec, rspec))
    return jax.jit(fn)


def build_sharded_histogram_fit(mesh: Mesh,
                                cfg: F.FCMConfig = F.FCMConfig(),
                                n_bins: int = 256):
    """Histogram-compressed distributed fit: ONE psum of 256 floats total,
    then the per-iteration loop is fully local/replicated."""
    axes = mesh_axes(mesh)
    xspec = P(axes)
    rspec = P()
    c, m = cfg.n_clusters, cfg.m

    def local_fit(x, w):
        idx = jnp.clip(x.astype(jnp.int32), 0, n_bins - 1)
        hist = jnp.zeros((n_bins,), jnp.float32).at[idx].add(w)
        hist = jax.lax.psum(hist, axes)            # the only O(bins) psum
        vals = jnp.arange(n_bins, dtype=jnp.float32)
        nz = hist > 0
        lo = jnp.min(jnp.where(nz, vals, jnp.asarray(3.4e38)))
        hi = jnp.max(jnp.where(nz, vals, jnp.asarray(-3.4e38)))
        frac = (jnp.arange(c, dtype=jnp.float32) + 0.5) / c
        v0 = lo + frac * (hi - lo)
        eps_v = cfg.eps * jnp.maximum(hi - lo, 1.0) * 0.1

        # Post-psum the loop is fully local/replicated: plain weighted
        # FCM over 256 rows, driven by the solver core's loop.
        v, delta, it = SV.while_centers(
            lambda v: H.weighted_center_step(vals, hist, v, m),
            v0, eps_v, cfg.max_iters)
        labels = F.labels_from_centers(x, v)
        return v, labels, delta, it

    fn = shard_map(local_fit, mesh=mesh,
                   in_specs=(xspec, xspec),
                   out_specs=(rspec, xspec, rspec, rspec))
    return jax.jit(fn)


def fit_sharded(x, mesh: Mesh, cfg: F.FCMConfig = F.FCMConfig(),
                histogram: bool = False) -> F.FCMResult:
    """Eager entry point: pads, shards, fits, unpads."""
    n = x.shape[0]
    xp, w = pad_to_devices(x, mesh.size)
    sharding = NamedSharding(mesh, P(mesh_axes(mesh)))
    xp = jax.device_put(xp, sharding)
    w = jax.device_put(w, sharding)
    fit = (build_sharded_histogram_fit if histogram
           else build_sharded_fit)(mesh, cfg)
    v, labels, delta, it = fit(xp, w)
    # Unpad on the host: on a mesh with explicit axes (``jax.make_mesh``'s
    # default) slicing the sharded labels would need an output sharding.
    return F.FCMResult(centers=v, labels=jax.device_get(labels)[:n],
                       n_iters=int(it), final_delta=float(delta))
