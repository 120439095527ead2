#!/usr/bin/env python3
"""The control of a line store's holes: the marks left out of the KERNEL's
view, which has to come out as NOT correct.

``promdev_prom_miss_1m`` states "a missed scrape is not a sample". The step
that would tempt a later PR is a kernel that reads a row's cells as one run
again — a count by subtraction, an increment from the cell before, the
filled planes gone. Here the program runs with exactly that view: the store
keeps what it has (its stamps and values read back exactly, its absent
steps stay absent), and ``line_info`` hands the fused tier a residual block
in which every hole mark reads 0, and says the store has no holes — so the
line kernel of ``adhoc_prom`` runs and takes every missed scrape for a
sample on its line (of the small number its value cell holds: the marker's
stamp less the line's). Only a probe that the fused kernel answers
can tell: the exact ``sum(count_over_time(m{g=..}[5m]))``. Through the whole
of ``run.run``; prints the numbers compared and whether ``correct`` came
out false. Never prints a result line.

    python3 benchmark/control_holes.py --workload adhoc_prom_miss --seed N \
        --seconds S
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def blind_kernel() -> None:
    """The fused tier's view of a line store with its hole marks cleared
    (one block a state of the store, made by the first query after a
    flush); the store keeps what it has."""
    import dataclasses
    import jax.numpy as jnp
    from filodb_tpu.core.chunkstore import RES_HOLE, SeriesStore
    line_info, kept = SeriesStore.line_info, {}

    def without_marks(self):
        info = line_info(self)
        if info is None:
            return None
        if kept.get("of") is not self.res:
            kept["of"] = self.res
            kept["res"] = jnp.where(self.res == RES_HOLE, 0, self.res)
        return dataclasses.replace(info, res=kept["res"], holes=False)

    SeriesStore.line_info = without_marks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from benchmark import run
    device = run.find_device(run.chips_of(a.workload))
    blind_kernel()
    args = argparse.Namespace(workload=a.workload, seed=a.seed,
                              seconds=a.seconds, trace=a.trace)
    res = run.run(args, device, strict_setup=False)
    verdict = ("set-up refused it" if res is None
               else f"correct = {res['correct']} {res['compared']}")
    print(f"control(kernel, hole marks left out): {verdict} (has to be "
          f"not correct)", flush=True)
    return 0 if (res is None or res["correct"] is False) else 1


if __name__ == "__main__":
    sys.exit(main())
