#!/usr/bin/env python3
"""The control of a line store's stamps: the residual left out, which has
to come out as NOT correct.

``promdev_prom_1m`` states "a sample is stored under the stamp it came
with". The step that would tempt a later PR is to keep the line and drop
the residual — one byte a cell less to store, to flush and to stream, one
matmul less a tile. Here the program runs with exactly that: the write path
records every residual as 0 and the filled history's residuals are zeroed,
so a late scrape is stored ON its row's line. ``--level kernel`` leaves
the store as it is and hands the fused kernel a residual block of zeros
instead: the store's stamps read back exactly, and only a probe that the
fused kernel answers can tell. Through the whole of ``run.run`` (set-up's
exact read-back printed, not enforced, so that the window runs and its
answers are compared); prints the numbers compared and whether ``correct``
came out false. Never prints a result line.

``--level narrow`` is no control but the cell with rows OFF their lines,
which the law alone never gives: for the first live scrape (the one set-up
sends through the write path) a residual beyond 60 ms counts as one that
does not fit, so the rows whose line started that late (3 in 61 of the
late ones: 0.3 % of all) are demoted before the warm-up and stay so, a
standing minority (the filled history and the kernel's spread stay as
they are; NARROW_SCRAPES widens it to later scrapes: a minority that grows
through the window, whose general kernels then compile inside it at every
doubling of their padded row count). It has to stay
``correct`` — the demoted rows through the minority correction, their
stamps from the pool — and its numbers say what demotions cost at size
(``--trace 1`` for ``demoted_rows_pct``).

    python3 benchmark/control_stamps.py --workload adhoc_prom --seed N \
        --seconds S [--level store|kernel|narrow] [--trace 0|1]
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def drop_residuals(data) -> None:
    """The write path and the data module's fill, with the residual left
    out (patched here, for this process only)."""
    import jax.numpy as jnp
    from filodb_tpu.core.chunkstore import SeriesStore
    track = SeriesStore._track_stamps

    def on_the_line(self, *a, **kw):
        res = track(self, *a, **kw)
        return None if res is None else res * 0

    SeriesStore._track_stamps = on_the_line
    fill = data.fill

    def fill_then_zero(shard, sid, seed, deploy):
        fill(shard, sid, seed, deploy)
        with shard.lock:
            shard.store.res = jnp.zeros_like(shard.store.res)

    data.fill = fill_then_zero


def blind_kernel() -> None:
    """The fused tier's view of a line store, with the residuals left out:
    ``line_info`` hands it zeros (one block, made once) and the store keeps
    what it has."""
    import dataclasses
    import jax.numpy as jnp
    from filodb_tpu.core.chunkstore import SeriesStore
    line_info, zeros = SeriesStore.line_info, {}

    def without_residuals(self):
        info = line_info(self)
        if info is None:
            return None
        if self.res.shape not in zeros:
            zeros[self.res.shape] = jnp.zeros_like(self.res)
        return dataclasses.replace(info, res=zeros[self.res.shape])

    SeriesStore.line_info = without_residuals


NARROW_MS = 60
NARROW_SCRAPES = 1


def narrow_width(fill: int) -> None:
    """The write path with a narrower idea of what fits, for the samples
    of columns ``fill .. fill + NARROW_SCRAPES - 1``: a row whose new
    residual lies beyond NARROW_MS is demoted (reason "residual") as one
    beyond the int8 width is, its stamps to the pool."""
    import numpy as np
    from filodb_tpu.core.chunkstore import SeriesStore
    track = SeriesStore._track_stamps

    def narrower(self, r, t, cols, uniq, first_pos):
        res = track(self, r, t, cols, uniq, first_pos)
        if res is None:
            return None
        wide = ((np.abs(res.astype(np.int64)) > NARROW_MS)
                & ~self.off_line[r] & (cols >= fill)
                & (cols < fill + NARROW_SCRAPES))
        if wide.any():
            rows = np.unique(r[wide])
            self._demote(rows, np.full(len(rows), "residual"))
            out = self.off_line[r]
            self._pool_ts[self._pool_slot[r[out]], cols[out]] = t[out]
            self._pool_dev = None
            res = np.where(out, 0, res).astype(res.dtype)
        return res

    SeriesStore._track_stamps = narrower


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--level", choices=("store", "kernel", "narrow"),
                    default="store")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from benchmark import run
    device = run.find_device(run.chips_of(a.workload))
    if a.level == "kernel":
        blind_kernel()
    elif a.level == "narrow":
        narrow_width(int(run.load_cell(a.workload)[2]["fill_columns"]))
    else:
        drop_residuals(run.load_cell(a.workload)[4])
    args = argparse.Namespace(workload=a.workload, seed=a.seed,
                              seconds=a.seconds, trace=a.trace)
    res = run.run(args, device, strict_setup=a.level == "narrow")
    if a.level == "narrow":
        if res is None:
            print("narrow: set-up refused it", flush=True)
            return 1
        print(f"narrow (residual width {NARROW_MS} ms): correct = "
              f"{res['correct']} {res['compared']}; failed {res['failed']} "
              f"of {res['attempted']}; "
              + ", ".join(f"{k} = {v['value']}"
                          for k, v in res["metrics"].items())
              + f"; device {res['device']}; breakdown "
              f"{res.get('breakdown')}", flush=True)
        return 0 if res["correct"] else 1
    verdict = ("set-up refused it" if res is None
               else f"correct = {res['correct']} {res['compared']}")
    print(f"control({a.level}, residuals left out): {verdict} (has to be "
          f"not correct)", flush=True)
    return 0 if (res is None or res["correct"] is False) else 1


if __name__ == "__main__":
    sys.exit(main())
