"""Parse and plan: ``query.parse`` + ``query.plan`` spans per query, median."""

from benchmark.layers import _spans


def read(ctx):
    return _spans.median(_spans.per_trace_ms(
        ctx["spans"], ("query.parse", "query.plan")))
