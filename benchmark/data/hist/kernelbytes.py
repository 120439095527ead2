"""Bytes a fused histogram-quantile query has to read, from its shapes.

The raw hist kernel streams each store row once over the columns its
windows touch — it slices columns, as the scalar kernel does: for
``fn(h[w])`` at steps ``out_ts`` that is the first window's first cell to
the last window's last cell, every bucket of them at the store's 4-byte
width. Per row also its sample count and group id (i32 each); the two band
operands (``[columns, steps]`` f32) are read once a call. The program
rounds the column range out to 128-column blocks
(``ops/fusedgrid.active_columns``); that rounding is the kernel's own cost
and is NOT counted as needed. A kernel that could not slice would need the
whole capacity: this one can.
"""

from __future__ import annotations

import numpy as np

from . import reference


def needed_columns(out_ts, window_ms: int, iv_ms: int, head_col: int,
                   capacity: int) -> int:
    lo, hi = reference.window_cells(out_ts, window_ms, iv_ms)
    hi = np.minimum(hi, min(head_col, capacity - 1))
    ok = hi >= lo
    if not ok.any():
        return 0
    return int(hi[ok].max() - lo[ok].min() + 1)


def query_bytes(rows: int, out_ts, window_ms: int, iv_ms: int, head_col: int,
                capacity: int, nb: int, value_bytes: int = 4) -> float:
    cols = needed_columns(out_ts, window_ms, iv_ms, head_col, capacity)
    steps = len(np.asarray(out_ts))
    return float(rows * (cols * nb * value_bytes + 8) + 2 * cols * steps * 4)
