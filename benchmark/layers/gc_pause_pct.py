"""Runtime: share of the window the process spent in full (generation 2)
garbage collections, every thread stopped (``runtime.gc`` spans that start
in the window, over its length on the trace clock)."""


def read(ctx):
    secs = [s["dur_s"] for s in ctx["spans"] if s["name"] == "runtime.gc"]
    window_s = (ctx["w1_ns"] - ctx["w0_ns"]) / 1e9
    if not secs or window_s <= 0:
        return None
    return 100.0 * sum(secs) / window_s
