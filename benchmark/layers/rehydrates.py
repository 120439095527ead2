"""Write path, flush: how often the store was decoded back to its raw f32 +
s64 blocks inside the window. Every ``ingest.flush`` span carries
``rehydrates``, the store's count since the flush before (an append, an
age-out or a free of a form that cannot take it; a stamp off the grid; the
cohort gate). Their sum over the window: 0 in a sound run — at 2^20 x 4,608
one rehydrate is 58 GB and the end of the node. None where no flush span
carries the tag."""


def read(ctx):
    xs = [int(s["tags"]["rehydrates"]) for s in ctx["spans"]
          if s["name"] == "ingest.flush" and "rehydrates" in s["tags"]]
    return float(sum(xs)) if xs else None
