"""HTTP front end: median client latency minus the median root ``query``
span — socket, request parsing, the scheduler's queue, JSON rendering."""

from benchmark.layers import _spans


def read(ctx):
    root = [s["dur_s"] * 1e3 for s in ctx["spans"] if s["name"] == "query"]
    lat = [(r["t1"] - r["t0"]) * 1e3 for r in ctx["records"]]
    if not root or not lat:
        return None
    return _spans.median(lat) - _spans.median(root)
