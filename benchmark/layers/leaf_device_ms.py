"""What the device does a query where no Pallas kernel runs: the time of
EVERY operation event of the traced part of the window (the "XLA Ops" line,
chips added up), per query answered in that part. ``kernel_ms`` counts
``tpu_custom_call`` events alone and reads 0 in a cell whose leaves gather a
few rows and run the general kernels. The write path's programs (the
flush's per-row selects) run on the same chip and are in the sum: the
line's ``breakdown.device_ops`` names them beside the queries' own."""

from benchmark import tracedata


def read(ctx):
    if not ctx["done_traced"]:
        return None
    t0, t1 = ctx["tw0_ns"], ctx["w1_ns"]
    ns = sum(d for p in tracedata.device_planes(ctx["trace"])
             for _n, s, d in tracedata.op_events(p) if t0 <= s < t1)
    return ns / 1e6 / len(ctx["done_traced"])
