"""95th percentile (nearest rank) of the latency of every read due in
the window, from its due time to the return of the `answer_batch` that
served it."""
from bench.numbers import nearest_rank


def read(ctx):
    return nearest_rank(ctx.read_latencies_ms(), 95)
