"""Bytes a fused window-aggregate query has to read from a line store.

The kernel streams each selected row once over the columns its windows
touch: per row those columns of the f32 value block AND of the int8
residual block (the stamps, as stored: one byte a cell), plus the row's
sample count, group id and line start (i32 each). A window's first and last
cell differ from series to series by one (every target has its own phase),
so the needed columns are the first window's earliest possible first cell
to the last window's latest possible last cell: ``counter``'s range plus
one cell. The band and edge operands are read once per call. The program's
rounding of the column range to 128-column blocks and the two further edge
cells it reads a side are the kernel's own cost, NOT counted as needed.
"""

from __future__ import annotations

import numpy as np

from . import datagen

VALUE_BYTES = 4
RESIDUAL_BYTES = 1


def needed_columns(out_ts, window_ms: int, iv_ms: int, head_col: int,
                   capacity: int) -> int:
    """Columns from the earliest first cell to the latest last cell of any
    series' windows: a stamp of scrape k lies in [nominal(k), nominal(k) +
    iv + LATE_MAX)."""
    t = np.asarray(out_ts, np.int64) - datagen.BASE_TS
    spread = iv_ms - 1 + datagen.LATE_MAX
    lo = np.maximum(-((-(t - window_ms - spread)) // iv_ms), 0)
    hi = np.minimum(t // iv_ms, min(head_col, capacity - 1))
    ok = hi >= lo
    if not ok.any():
        return 0
    return int(hi[ok].max() - lo[ok].min() + 1)


def query_bytes(rows: int, out_ts, window_ms: int, iv_ms: int, head_col: int,
                capacity: int) -> float:
    cols = needed_columns(out_ts, window_ms, iv_ms, head_col, capacity)
    steps = len(np.asarray(out_ts))
    return float(rows * (cols * (VALUE_BYTES + RESIDUAL_BYTES) + 12)
                 + 2 * cols * steps * 4)
