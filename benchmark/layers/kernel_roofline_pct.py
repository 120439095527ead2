"""Fused kernel: the bytes its queries need (the deployment's data module's
``query_bytes``, from their shapes and at the store's own value width) over
the chip's HBM bandwidth, as a share of the kernels' device time. Bound by
bytes: the kernel's matmuls are banded selections.

Queries count that were answered inside the window on a fused route; the
kernel time is every Pallas event of the window, so kernels of queries
still in flight at either edge count as time and not as bytes: the share
reads low by at most that, never high."""

from benchmark.layers import _kernels


def read(ctx):
    ev = _kernels.events(ctx)
    q = [r for r in _kernels.executed(ctx) if "fused" in (r["path"] or "")
         and not (r["path"] or "").startswith("incremental")]
    if not ev or not q:
        return None
    need = 0.0
    for r in q:
        ref = ctx["mix"]["queries"][r["req"].qi]["ref"]
        for rows in ctx["rows_per_shard"]:
            need += ctx["data"].query_bytes(
                rows, ref, r["req"].out_ts(), ctx["deploy"], ctx["head_col"],
                ctx["capacity"])
    secs = sum(e[3] for e in ev) / 1e9
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / secs
