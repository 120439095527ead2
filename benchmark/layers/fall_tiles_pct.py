"""Fused kernel: the share of a line rate program's row tiles that fell back
to the band product. On a line store the rate family takes a window's sure
delta as the difference of two values the tile picks anyway, and runs the
band product over the increments only in a tile where a counter fell among
the cells some window sums, or a row ends under a window. Over the fetch
spans that carry BOTH tags (a line rate program's; the fused-hist route's
carry ``fall_tiles`` alone and are not read): 100 x the sum of their
``fall_tiles`` over the sum of their ``tiles`` (the grid steps of the
dispatch): 0 in a sound run of ``adhoc_prom`` and ``adhoc_prom_miss``,
whose counters never fall. None where no fetch span carries ``tiles`` (the
program at a commit that sums every tile's increments)."""


def read(ctx):
    both = [(float(s["tags"]["fall_tiles"]), float(s["tags"]["tiles"]))
            for s in ctx["spans"]
            if s["name"] == "query.exec.kernel"
            and s["tags"].get("phase") == "fetch"
            and "fall_tiles" in s["tags"] and "tiles" in s["tags"]]
    tiles = sum(t for _, t in both)
    if not both or not tiles:
        return None
    return 100.0 * sum(f for f, _ in both) / tiles
