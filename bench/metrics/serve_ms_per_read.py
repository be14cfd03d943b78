"""Milliseconds of `answer_batch` per read, less the maintenance it ran:
the fused program's re-run, answer assembly and the serving ladder,
summed over the window's read batches and divided by their reads."""


def read(ctx):
    batches = ctx.rec.batches
    n = sum(len(b.reads) for b in batches)
    if not n:
        return None
    return sum(b.end - b.start - b.maint_s for b in batches) / n * 1e3
