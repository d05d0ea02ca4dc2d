"""Analytic work of each kernel kind: operations and the least HBM
traffic the algorithm needs, whatever implements it.

Counts are per real request (padding lanes do no useful work) and use
each request's own iterations. Bytes are the payload in once and the
kernel's result out once: a kernel that streams its rows from HBM on
every iteration moves more, and that extra is what its roofline share
shows. Operation counts per (row or pixel, cluster, iteration):

- ``bin``: one increment per pixel; uint8 pixels in, ``n_bins`` float32
  counts out.
- ``flat`` (weighted FCM over ``rows`` values): distance 3, membership
  6 (power, reciprocal, normalise), weighted partial sums 2 (D + 1),
  with D = 1: 13. Rows and weights in as float32, centers out.
- ``stencil`` (FCM_S over an H x W grid, ``nb`` neighbours): the
  neighbour sums 2 nb per pixel, plus per cluster the centre and
  neighbour distance terms and membership, 10 + nb. The image in as
  float32, centers out.
- ``gather`` (the histogram route's labelling): one lookup per pixel in
  the request's table of ``rows`` labels; uint8 pixels and the int32
  table in, one int32 label a pixel out. No arithmetic: its share is a
  memory roofline.
"""
from __future__ import annotations

F32 = 4


def kernel_work(kind: str, *, iters, c: int, pixels: int = 0,
                rows: int = 0, neighbors: int = 0, n_bins: int = 256):
    """(flops, bytes) of ``kind`` over requests with iterations
    ``iters`` (a sequence, one per request)."""
    n = len(iters)
    total_iters = float(sum(iters))
    if kind == "bin":
        return float(n * pixels), float(n * (pixels + F32 * n_bins))
    if kind == "flat":
        return (float(rows * c * 13) * total_iters,
                float(n * F32 * (2 * rows + c)))
    if kind == "stencil":
        per = 2 * neighbors + c * (10 + neighbors)
        return (float(pixels * per) * total_iters,
                float(n * F32 * (pixels + c)))
    if kind == "gather":
        return float(n * pixels), float(n * (pixels + F32 * (rows + pixels)))
    raise ValueError(f"no cost model for kernel kind {kind!r}")


def roofline_seconds(flops: float, bytes_: float, peak) -> tuple:
    """(least seconds, which side bounds it) on one chip."""
    if flops <= 0 or bytes_ <= 0:
        raise ValueError(f"roofline of zero work: {flops} FLOP, "
                         f"{bytes_} bytes")
    t_c = flops / float(peak["flops_per_s"])
    t_m = bytes_ / float(peak["hbm_bytes_per_s"])
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
