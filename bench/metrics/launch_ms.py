"""Route program: the fenced launch per batch, in ms: delta
route.stage_seconds{stage=solve} / delta route.batches."""


def read(ctx):
    n = ctx.delta("batches")
    return 1e3 * ctx.delta("solve_s") / n if n > 0 else None
