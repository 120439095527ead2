"""HTTP front end: wait in the scheduler's heap (``query.queue``, enqueue to
a worker taking it), summed over the window, per query."""

from benchmark.layers import _means


def read(ctx):
    return _means.per_query_ms(ctx, "query.queue")
