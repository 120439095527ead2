"""Leaf under the shard lock: the share of selects that ran the index's
time-masked pass. Where every series lives through a query's range
(``PartKeyIndex.all_live_through``) a selector's part ids are a function of
the labels alone and the selection memo serves them; a series that starts
after a query's end, or that has been marked ended, makes the select gather
every matching entry's start and end time (13 ms over 1.03 M entries) —
the select span then says ``memo_why`` = ``time_mask``. The memo keeps what
that pass left for the span of ranges that it leaves the same
(``core/selection.py``), so on a fleet that redeploys only the first query
of each set of births and ends pays it: near 0 in a sound run, 100 where
every query pays it (the program as it was when the cell was first
measured: 34 q/s where it now gives the rate of a store without churn),
and 100 at a size where every selection is narrower than a gather and
none is kept (the CPU rehearsal's). 100 x the select spans whose
``memo_why`` reads ``time_mask`` over the select spans of the window's
queries. None where the window holds no select span or none carries
``memo`` (the program before the memo)."""


def read(ctx):
    spans = [s for s in ctx["spans"]
             if s["name"] == "query.exec.select" and "memo" in s["tags"]]
    if not spans:
        return None
    masked = sum(s["tags"].get("memo_why") == "time_mask" for s in spans)
    return 100.0 * masked / len(spans)
