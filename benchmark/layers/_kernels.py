"""Shared by the kernel readers: the Pallas kernels' device events of the
traced part of the window (all of it on one chip; benchmark/run.py,
TRACE_CHIP_SECONDS), and the queries answered in that part."""

from benchmark import tracedata

# routes answered whole from a cache: no leaf, no device operation
CACHE_ANSWERS = ("result-cache", "fragment-cache", "negative-cache")


def events(ctx):
    return tracedata.named_events(ctx["trace"], ctx["tw0_ns"], ctx["w1_ns"],
                                   ctx["peaks"]["kernel_names"])


def executed(ctx):
    """Answers that executed a leaf: every route but a hit that is
    answered whole from a cache."""
    return [r for r in ctx["done_traced"]
            if not (r["path"] or "").startswith(CACHE_ANSWERS)]
