"""Fused kernel: the share of a fused answer's rows that the general kernels
made. A store that keeps stamps as line + residual takes the rows that do
not fit their line off it (a residual beyond the width, a skipped cell, a
changed interval); the fused kernel skips them and the general kernels
answer them over gathered rows. Over the queries a fused kernel answered:
100 x the sum of their select spans' ``demoted`` tags (the selected rows
off their line) over the sum of their dispatch spans' ``rows``: 0 in a
sound run of ``adhoc_prom``. None where no select span of such a query
carries the tag (the program at a commit that has no line form)."""


def read(ctx):
    rows = {}
    for s in ctx["spans"]:
        if (s["name"] == "query.exec.kernel"
                and s["tags"].get("phase") == "dispatch"):
            rows[s["trace_id"]] = (rows.get(s["trace_id"], 0.0)
                                   + float(s["tags"].get("rows", 0)))
    demoted = [(s["trace_id"], float(s["tags"]["demoted"]))
               for s in ctx["spans"]
               if s["name"] == "query.exec.select" and "demoted" in s["tags"]
               and rows.get(s["trace_id"])]
    if not demoted:
        return None
    return (100.0 * sum(d for _, d in demoted)
            / sum(rows[t] for t in {t for t, _ in demoted}))
