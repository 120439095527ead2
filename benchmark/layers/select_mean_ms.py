"""Leaf under the shard lock: index select + array capture
(``query.exec.select``; one a shard on the mesh route), per query."""

from benchmark.layers import _means


def read(ctx):
    return _means.per_query_ms(ctx, "query.exec.select")
