"""Write path, consume: rows moved per second of ``ingest.consume`` span."""


def read(ctx):
    sp = [s for s in ctx["spans"] if s["name"] == "ingest.consume"]
    secs = sum(s["dur_s"] for s in sp)
    rows = sum(int(s["tags"].get("rows", 0)) for s in sp)
    if not sp or secs <= 0 or rows <= 0:
        return None
    return rows / secs
