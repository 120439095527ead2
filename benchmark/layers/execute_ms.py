"""Plan execution (local fused, scatter-gather or the mesh program, and the
reduce): ``query.execute`` spans per query, median."""

from benchmark.layers import _spans


def read(ctx):
    return _spans.median(_spans.per_trace_ms(ctx["spans"],
                                             ("query.execute",)))
