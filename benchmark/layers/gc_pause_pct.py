"""Runtime: share of the window the process spent in full (generation 2)
garbage collections, every thread stopped (``runtime.gc`` spans that start
in the window, over its length on the trace clock). A window whose program
recorded spans and no collection reads 0; only a window with no span at all
(the tracer off) has nothing to read."""


def read(ctx):
    window_s = (ctx["w1_ns"] - ctx["w0_ns"]) / 1e9
    if not ctx["spans"] or window_s <= 0:
        return None
    secs = [s["dur_s"] for s in ctx["spans"] if s["name"] == "runtime.gc"]
    return 100.0 * sum(secs) / window_s
