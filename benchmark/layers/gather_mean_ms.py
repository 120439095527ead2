"""Leaf under the shard lock: the row gather of a narrow selection
(``query.exec.gather``, inside the select span: values, counts and stamps of
the selected rows, padded to a power of two), per query. None where the
window holds no such span — the program at a commit that records none, or a
mix whose selections are all wide."""

from benchmark.layers import _means


def read(ctx):
    return _means.per_query_ms(ctx, "query.exec.gather")
