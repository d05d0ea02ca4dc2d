"""Batching: real lanes over bucket lanes launched in the window, in %:
delta route.images / (delta route.images + delta route.padded)."""


def read(ctx):
    real, pad = ctx.delta("images"), ctx.delta("padded")
    return 100.0 * real / (real + pad) if real > 0 else None
