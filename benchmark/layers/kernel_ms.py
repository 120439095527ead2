"""Fused kernel: device time of the Pallas calls, from the profiler's trace,
per query answered in the traced part of the window (a query answered from
a cache counts with 0; kernels on four shards add up, they are not
averaged)."""

from benchmark.layers import _kernels


def read(ctx):
    if not ctx["done_traced"]:
        return None
    return (sum(e[3] for e in _kernels.events(ctx)) / 1e6
            / len(ctx["done_traced"]))
