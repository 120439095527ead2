"""Leaf under the shard lock: the share of leaf selects for which the index
had to RESOLVE the filter set — matcher set algebra, regex value sets among
it — because its filter cache (and the selection memo) missed: 100 x the
select spans whose ``resolve`` tag reads ``miss`` over those that carry the
tag. 0 in a mix whose warm-up sends every text once (``tsbs_single``); a
fresh host draw a query would read 100. None where no select span carries
the tag (the program at a commit that does not record it)."""

from benchmark.layers import _means


def read(ctx):
    ids = _means.query_traces(ctx["spans"])
    how = [s["tags"]["resolve"] for s in ctx["spans"]
           if s["name"] == "query.exec.select" and s["trace_id"] in ids
           and "resolve" in s["tags"]]
    if not how:
        return None
    return 100.0 * sum(h == "miss" for h in how) / len(how)
