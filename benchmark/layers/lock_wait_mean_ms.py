"""Leaf under the shard lock: what the leaves' threads waited for shard
locks (the ``lock_wait_ms`` tag of ``query.exec.leaf``), per query. A wait
is a tag and not a span: a waiting thread is not what the host was doing.
The epoch probe's wait before the leaf is not in it (the ``query`` span's
own ``lock_wait_ms`` tag holds both)."""

from benchmark.layers import _means


def read(ctx):
    return _means.per_query_ms(ctx, "query.exec.leaf",
                               _means.tag_ms("lock_wait_ms"))
