"""Write path, consume: share of the ``ingest.consume`` spans' time their
threads spent waiting for the shard lock (their ``lock_wait_ms`` tag, the
flushes nested in them included)."""


def read(ctx):
    sp = [s for s in ctx["spans"] if s["name"] == "ingest.consume"
          and "lock_wait_ms" in s["tags"]]
    total_ms = sum(s["dur_s"] for s in sp) * 1e3
    if not sp or total_ms <= 0:
        return None
    return 100.0 * sum(float(s["tags"]["lock_wait_ms"]) for s in sp) / total_ms
