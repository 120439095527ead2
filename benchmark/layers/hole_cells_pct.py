"""Fused kernel: the share of the cells a fused answer's rows use that hold
no sample. A line store keeps a missed scrape (a staleness marker, a
skipped cell) as a hole in its cell, and the fused kernel reads around it.
Over the queries a fused kernel answered: 100 x the sum of their select
spans' ``hole_cells`` tags (the selected rows' cells without a sample, from
the host's counts) over the sum of the same spans' ``used_cells``: 0.78 in
a sound run of ``adhoc_prom_miss`` (1 scrape in 128). None where no select
span of such a query carries the tags (the program at a commit that keeps
no holes)."""


def read(ctx):
    fused = {s["trace_id"] for s in ctx["spans"]
             if s["name"] == "query.exec.kernel"
             and s["tags"].get("phase") == "dispatch"}
    cells = [(float(s["tags"]["hole_cells"]), float(s["tags"]["used_cells"]))
             for s in ctx["spans"]
             if s["name"] == "query.exec.select" and s["trace_id"] in fused
             and "hole_cells" in s["tags"] and "used_cells" in s["tags"]]
    used = sum(u for _, u in cells)
    if not cells or not used:
        return None
    return 100.0 * sum(h for h, _ in cells) / used
