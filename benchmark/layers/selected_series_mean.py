"""Leaf under the shard lock: the series a leaf selected, mean over the
window's leaves that GATHERED (the select span's ``series`` where its
``route`` tag reads ``gather``). The reader that shows a selective mix ran
as named: ``tsbs_single`` asks 1 host in six texts of twelve and 8 in the
other six, 4.5 series a query. None where no select span carries ``route``
(the program at a commit that does not tag it)."""

from benchmark.layers import _means


def read(ctx):
    ids = _means.query_traces(ctx["spans"])
    picked = [float(s["tags"].get("series", 0)) for s in ctx["spans"]
              if s["name"] == "query.exec.select" and s["trace_id"] in ids
              and s["tags"].get("route") == "gather"]
    if not picked:
        return None
    return sum(picked) / len(picked)
