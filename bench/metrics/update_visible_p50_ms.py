"""Median, over every update batch due in the window, of the time from
its due time to the return of the first `answer_batch` that applied it:
freshness as a reader feels it under a staleness budget of 0."""
from bench.numbers import nearest_rank


def read(ctx):
    return nearest_rank(ctx.update_visible_ms(), 50)
