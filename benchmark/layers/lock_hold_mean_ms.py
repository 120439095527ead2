"""Leaf under the shard lock, the HOLDER's side: what one query holds one
shard lock for, the epoch probe's hold and the leaf's (the ``query`` span's
``lock_hold_ms`` tag: every hold its thread released inside it, each once —
the leaf span's own tag repeats the leaf's share and is not added), per
query. On the mesh route the leaf takes every shard's lock and the tag is
the sum over them: divided by the ``locks`` tag of the trace's leaf. None
where no ``query`` span carries the tag (the parent)."""

from benchmark.layers import _means


def read(ctx):
    locks = {s["trace_id"]: float(s["tags"]["locks"]) for s in ctx["spans"]
             if s["name"] == "query.exec.leaf" and "locks" in s["tags"]}
    held_ms = _means.tag_ms("lock_hold_ms")

    def per_lock_ms(s):
        v = held_ms(s)
        return None if v is None else v / locks.get(s["trace_id"], 1.0)

    return _means.per_query_ms(ctx, "query", per_lock_ms)
