"""Mean milliseconds of one maintenance pass in the window:
`ServeStats.maintenance_seconds` over `ServeStats.refreshes`, both as
deltas across the window's read batches."""


def read(ctx):
    batches = ctx.rec.batches
    passes = sum(b.passes for b in batches)
    if not passes:
        return None
    return sum(b.maint_s for b in batches) / passes * 1e3
