"""Solver: mean iterations per real lane in the window, from the
route.lane_iters histogram's sum and count."""


def read(ctx):
    n = ctx.delta("lane_iters_count")
    return ctx.delta("lane_iters_sum") / n if n > 0 else None
