"""Fused kernel, the host's side: dispatch under the lock plus the blocking
fetch of the result outside it (both phases of ``query.exec.kernel``), per
query; ``kernel_ms`` beside it is the device's side."""

from benchmark.layers import _means


def read(ctx):
    return _means.per_query_ms(ctx, "query.exec.kernel")
