"""Kernels: the label gather's share of its roofline, in %: the least
time the window's labelling needs on one chip (uint8 pixels and the
int32 label table in, int32 labels out; bench/lib/costs.py) over the
device time the gather fusions took."""


def read(ctx):
    return ctx.roofline_share("gather")
