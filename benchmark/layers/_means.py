"""Shared by the per-query mean readers: a layer's time summed over the
window, divided by the queries of the window.

A query is a trace that holds a ``query`` span; a span in no such trace (a
rule evaluation's, a collection's on an idle thread) is not counted. Means,
not medians: three query texts in four are fast unless they wait for the
shard lock, so a per-query median lands on either side of a bimodal
population from run to run, while sums add up (request = queue + query +
render + self; leaf = lock wait + select + group ids + kernel + self).
A reader returns None when the window holds nothing of its name — the
program at a commit that does not record it yet.
"""

from __future__ import annotations


def query_traces(spans) -> set:
    return {s["trace_id"] for s in spans if s["name"] == "query"}


def span_ms(s) -> float:
    return s["dur_s"] * 1e3


def tag_ms(tag: str):
    """Value of a span = a millisecond tag it carries (None without it)."""
    def value(s):
        v = s["tags"].get(tag)
        return None if v is None else float(v)
    return value


def per_query_ms(ctx, name: str, value=span_ms):
    ids = query_traces(ctx["spans"])
    vals = [value(s) for s in ctx["spans"]
            if s["name"] == name and s["trace_id"] in ids]
    vals = [v for v in vals if v is not None]
    if not ids or not vals:
        return None
    return sum(vals) / len(ids)
