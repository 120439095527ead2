"""Write path, flush: median duration of an ``ingest.flush`` span (a flush
that had staged rows to land: device scatter, backpressure, upkeep)."""

import statistics


def read(ctx):
    ms = [s["dur_s"] * 1e3 for s in ctx["spans"] if s["name"] == "ingest.flush"]
    return statistics.median(ms) if ms else None
