"""Shared by the span readers: a layer's time per query, and its median.

Every query has a root ``query`` span; a layer's time for a query is the
sum of that layer's spans in the query's trace, and 0 where the query never
entered the layer (a cache hit skips parse, plan and leaf). So a reader has
something to read whenever the window answered a query, and a median of 0
says that most queries skipped the layer.
"""

from __future__ import annotations

import numpy as np


def per_trace_ms(spans, names) -> list[float]:
    acc = {s["trace_id"]: 0.0 for s in spans if s["name"] == "query"}
    for s in spans:
        if s["name"] in names and s["trace_id"] in acc:
            acc[s["trace_id"]] += s["dur_s"] * 1e3
    return list(acc.values())


def median(xs):
    return float(np.median(xs)) if len(xs) else None
