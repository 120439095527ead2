"""Share of the window's answers that the fragment cache served or
extended (``stats.exec_path`` names ``incremental`` or ``fragment-cache``).
A ``result-cache`` answer is not counted here: no mix repeats a range, so
one is a fault of the traffic and counts in ``routes_off``."""

CACHE_ROUTES = ("incremental", "fragment-cache")


def read(ctx):
    recs = ctx["records"]
    if not recs:
        return None
    hit = sum((r["path"] or "").startswith(CACHE_ROUTES) for r in recs)
    return 100.0 * hit / len(recs)
