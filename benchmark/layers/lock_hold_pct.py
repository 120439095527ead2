"""Leaf under the shard lock, the LOCK's side: the busiest shard lock's
utilisation. Once a second the program's heartbeat (``runtime.beat``) tags
the shard lock whose ``hold_s`` grew most over its period with that growth
(``lock_hold_ms``: every hold once, whoever held it — queries, consumer,
flush — and per lock on the mesh) and the period's length (``period_ms``;
1000 where a beat does not say). 100 x the sum of the growths over the sum
of the periods. None where the window holds no such beat (the program at a
commit without the heartbeat, or the tracer off)."""


def read(ctx):
    beats = [s["tags"] for s in ctx["spans"]
             if s["name"] == "runtime.beat" and "lock_hold_ms" in s["tags"]]
    period_ms = sum(float(t.get("period_ms", 1000.0)) for t in beats)
    if period_ms <= 0:
        return None
    return 100.0 * sum(float(t["lock_hold_ms"]) for t in beats) / period_ms
