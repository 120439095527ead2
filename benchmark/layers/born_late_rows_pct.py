"""Fused kernel: the share of a fused answer's rows that were born late. A
store in time-aligned cells writes a series that appears after the grid's
first cell from its BIRTH cell on, and the fused grid program reads such
rows in its births mode. Over the window's queries: 100 x the sum of their
dispatch spans' ``born_late`` tags (the SELECTED rows whose birth cell is
past the grid's first; a card's time mask selects the series that had
started by its end, so the value depends on the deck) over the sum of the
same spans' ``rows`` (the store's). 0 says that the fill or the store lost
the births, and the cell measures a store that never redeploys. None where
no dispatch span carries the tag (the program at a commit without birth
cells)."""


def read(ctx):
    spans = [s for s in ctx["spans"]
             if s["name"] == "query.exec.kernel"
             and s["tags"].get("phase") == "dispatch"
             and "born_late" in s["tags"] and s["tags"].get("rows")]
    if not spans:
        return None
    return (100.0 * sum(float(s["tags"]["born_late"]) for s in spans)
            / sum(float(s["tags"]["rows"]) for s in spans))
