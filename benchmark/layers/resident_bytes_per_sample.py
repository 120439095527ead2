"""Write path, flush: what a cell of the store costs in HBM. Every
``ingest.flush`` span carries ``sample_bytes`` — the store's resident bytes
of values and stamps over the cells it holds (``S x C``), as that flush
left them: 12 for raw f32 + s64, ~1.0 for the delta8 form with elided
stamps. The median over the window's flushes; a store that was decoded
back to raw for an append and re-encoded after it reads 12 in between and
the median shows it. None where no flush span carries the tag (the program
at a commit before it)."""

import statistics


def read(ctx):
    xs = [float(s["tags"]["sample_bytes"]) for s in ctx["spans"]
          if s["name"] == "ingest.flush" and "sample_bytes" in s["tags"]]
    return statistics.median(xs) if xs else None
