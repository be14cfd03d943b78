"""95th percentile (nearest rank) of the time a read waited in the
driver's queue, from its due time to the start of its `answer_batch`."""
from bench.numbers import nearest_rank


def read(ctx):
    rec = ctx.rec
    waits = [(d - due) * 1e3 for due, d in zip(rec.due, rec.dispatch)
             if d is not None]
    return nearest_rank(waits, 95)
