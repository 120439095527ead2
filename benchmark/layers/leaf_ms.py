"""The leaf under its shard lock (index select, group ids, dispatch, wait):
``query.exec.leaf`` spans per query, median."""

from benchmark.layers import _spans


def read(ctx):
    return _spans.median(_spans.per_trace_ms(ctx["spans"],
                                             ("query.exec.leaf",)))
