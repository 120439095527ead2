"""Write path, flush: how often a flush appended to the store IN its narrow
form. Every ``ingest.flush`` span carries ``form``: ``narrow`` where the
append wrote deltas into the int8 block in place, ``raw`` where it wrote
f32 + s64 blocks (a raw store, or one decoded back for the append),
``rebuilt`` where the flush then re-encoded the whole store. 100 x the
window's flush spans whose ``form`` reads ``narrow`` over those that carry
the tag: 100 in a sound run of a compressed-resident cell. None where no
flush span carries the tag."""


def read(ctx):
    forms = [s["tags"]["form"] for s in ctx["spans"]
             if s["name"] == "ingest.flush" and "form" in s["tags"]]
    if not forms:
        return None
    return 100.0 * sum(f == "narrow" for f in forms) / len(forms)
