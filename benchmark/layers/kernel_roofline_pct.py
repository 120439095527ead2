"""Fused kernel: the bytes its queries need (benchmark/kernelbytes.py, from
their shapes) over the chip's HBM bandwidth, as a share of the kernels'
device time. Bound by bytes: the kernel's matmuls are banded selections.

Queries count that were answered inside the window on a fused route; the
kernel time is every Pallas event of the window, so kernels of queries
still in flight at either edge count as time and not as bytes: the share
reads low by at most that, never high."""

from benchmark import kernelbytes
from benchmark.layers import _kernels


def read(ctx):
    ev = _kernels.events(ctx)
    q = [r for r in _kernels.executed(ctx) if "fused" in (r["path"] or "")
         and not (r["path"] or "").startswith("incremental")]
    if not ev or not q:
        return None
    iv = int(ctx["deploy"]["scrape_interval_ms"])
    need = 0.0
    for r in q:
        ref = ctx["mix"]["queries"][r["req"].qi]["ref"]
        for rows in ctx["rows_per_shard"]:
            need += kernelbytes.query_bytes(
                rows, r["req"].out_ts(), int(ref["window_s"]) * 1000, iv,
                ctx["head_col"], ctx["capacity"])
    secs = sum(e[3] for e in ev) / 1e9
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / secs
