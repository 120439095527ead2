"""Shared by the kernel readers: the Pallas kernels' device events of the
window, and the queries that ran one."""

from benchmark import tracedata


def events(ctx):
    return tracedata.named_events(ctx["trace"], ctx["w0_ns"], ctx["w1_ns"],
                                   ctx["peaks"]["kernel_names"])


def executed(ctx):
    """Answers of the window that executed a leaf: every route but a hit
    that is answered whole from a cache."""
    return [r for r in ctx["done_in"]
            if not (r["path"] or "").startswith(
                ("result-cache", "fragment-cache", "negative-cache"))]
