#!/usr/bin/env python3
"""The controls of a store whose rows are born and end: the fused program's
view of the births, or of the ends, broken ALONE — each has to come out as
NOT correct.

``promdev_churn_1m`` states "a series exists from its first sample to its
last: no function reads a cell before the one or after the other". The
store is left intact (set-up's checks of it pass, a raw selector reads
right); what is broken is what the fused tier is handed:

- ``--fault born``: the fused program never hears of a birth cell
  (``born`` is withheld: the program as it was before there were birth
  cells runs): the cells before a row's birth count as samples of value 0.
- ``--fault ended``: every selected row is handed on as if it reached the
  newest cell any row holds: the cells after an ended series' last sample
  count as samples.

The timed answers hardly move (one row in nine gains a few cells in some
windows, under a sum over 10^6 rows); the read-back's exact count that the
FUSED kernel makes (``sum(count_over_time(m{g=..}[5m]))`` around an event)
is what catches both. Through the whole of ``run.run``; prints the numbers
compared and whether ``correct`` came out false. Never prints a result
line.

    python3 benchmark/control_births.py --workload adhoc_churn \\
        --fault born|ended --seed N --seconds S
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def break_the_fused_view(fault: str) -> None:
    """``fusedgrid.fused_grid_aggregate`` with its ``born`` withheld, or
    its ``n`` raised to the store's newest cell for every selected row."""
    import jax.numpy as jnp
    from filodb_tpu.ops import fusedgrid
    sound = fusedgrid.fused_grid_aggregate

    def faulty(op, fn, val, n, *a, **k):
        if fault == "born":
            k["born"] = None
        else:
            n = jnp.asarray(n)
            n = jnp.where(n > 0, jnp.max(n), 0).astype(n.dtype)
        return sound(op, fn, val, n, *a, **k)

    fusedgrid.fused_grid_aggregate = faulty


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=("born", "ended"), required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from benchmark import run
    device = run.find_device(run.chips_of(a.workload))
    break_the_fused_view(a.fault)
    args = argparse.Namespace(workload=a.workload, seed=a.seed,
                              seconds=a.seconds, trace=a.trace)
    res = run.run(args, device, strict_setup=False)
    verdict = ("set-up refused it" if res is None
               else f"correct = {res['correct']} {res['compared']}")
    print(f"control(births, fault {a.fault}): {verdict} (has to be not "
          f"correct)", flush=True)
    return 0 if (res is not None and res["correct"] is False) else 1


if __name__ == "__main__":
    sys.exit(main())
