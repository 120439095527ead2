"""Fused kernel, the device queue ahead of a dispatch: fused query programs
that were dispatched and not yet fetched as a query's program entered (the
dispatch span's ``ahead`` tag: what the device runs first), mean over the
dispatch spans of the window's queries. Between 0 and the other workers'
count by construction. None where no dispatch span carries the tag (the
parent)."""

from benchmark.layers import _means


def read(ctx):
    ids = _means.query_traces(ctx["spans"])
    ahead = [float(s["tags"]["ahead"]) for s in ctx["spans"]
             if s["name"] == "query.exec.kernel" and s["trace_id"] in ids
             and s["tags"].get("phase") == "dispatch" and "ahead" in s["tags"]]
    if not ahead:
        return None
    return sum(ahead) / len(ahead)
