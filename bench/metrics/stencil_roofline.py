"""Kernels: the 'stencil' kernels' share of their roofline, in %: the least
time the window's 'stencil' work needs on one chip (bench/lib/costs.py, the
peaks of bench/lib/peaks.py) over the device time those kernels took."""


def read(ctx):
    return ctx.roofline_share("stencil")
