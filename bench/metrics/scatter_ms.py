"""Route program: host scatter of a batch's results, in ms: delta
route.stage_seconds{stage=materialize} / delta route.batches."""


def read(ctx):
    n = ctx.delta("batches")
    return 1e3 * ctx.delta("materialize_s") / n if n > 0 else None
