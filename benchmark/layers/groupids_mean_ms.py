"""Leaf under the shard lock: the ``by``/``without`` group-id walk over the
selected series' keys (``query.exec.groupids``), per query — a query with
no grouping opens none and counts with 0."""

from benchmark.layers import _means


def read(ctx):
    return _means.per_query_ms(ctx, "query.exec.groupids")
