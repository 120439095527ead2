"""Leaf under the shard lock: how often a gathered leaf reached the device
as ONE program. A narrow selection's gather span (``query.exec.gather``)
carries ``programs``: 1 where the row gather, the window function, the step
slice and the aggregate's map phase were dispatched as one program with the
host's scalars as its arguments, more where the leaf kept its stepwise form
(a compressed-resident, line-form or histogram store, a churned cohort, rows
a fused kernel takes). 100 x the gather spans of the window's queries whose
``programs`` reads 1 over those that carry the tag: 100 in a sound run of
``tsbs_single``, whose store is a raw grid. None where no gather span of a
query carries the tag (the program at a commit that gathers on its own, or a
mix whose selections are all wide)."""

from benchmark.layers import _means


def read(ctx):
    ids = _means.query_traces(ctx["spans"])
    forms = [s["tags"]["programs"] for s in ctx["spans"]
             if s["name"] == "query.exec.gather" and s["trace_id"] in ids
             and "programs" in s["tags"]]
    if not forms:
        return None
    return 100.0 * sum(int(p) == 1 for p in forms) / len(forms)
